"""Batched data-plane primitives: grid-driven kernel entry points
(`kernels.ops.gf256_scale_batch` / `xor_reduce_segments`), the staging
of the premultiply's rows (`kernels.ops.stage_rows`), the lockstep
GF(256) Gauss-Jordan, and `RSCode.repair_coeffs_batch`.

Separate from tests/test_kernels.py and tests/test_rs.py on purpose:
those modules skip entirely without hypothesis, while everything here is
deterministic and must run on the bare-numpy tier-1 environment too.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import interpreted
from repro.ec import gf256
from repro.ec.rs import RSCode
from repro.kernels import gf256_scale, ops, ref, xor_reduce


# ------------------------------------------------------- gf256_scale_batch
@pytest.mark.parametrize("m,nbytes", [(1, 32), (5, 100), (16, 1024)])
def test_gf256_scale_batch_paths(m, nbytes, rng):
    """Batched per-row premultiply: numpy ref path and grid-driven kernel
    path (interpret) both equal the per-row table ground truth."""
    coeffs = rng.integers(0, 256, size=m, dtype=np.uint8)
    data = rng.integers(0, 256, size=(m, nbytes), dtype=np.uint8)
    want = np.stack([gf256.MUL_TABLE[coeffs[i], data[i]] for i in range(m)])
    got_ref = np.asarray(ops.gf256_scale_batch(coeffs, data))
    with interpreted():
        got_kernel = np.asarray(ops.gf256_scale_batch(coeffs, data))
    assert np.array_equal(got_ref, want)
    assert np.array_equal(got_kernel, want)
    # also matches m calls of the (m, k) matmul entry point
    per_row = np.concatenate([
        np.asarray(ops.gf256_matmul(coeffs[i: i + 1, None],
                                    jnp.asarray(data[i: i + 1])))
        for i in range(m)
    ])
    assert np.array_equal(per_row, want)


@pytest.mark.parametrize("nbytes", [1, 33, 1000, 4100])
@pytest.mark.parametrize("m", [1, 256])
def test_gf256_scale_batch_kernel_byte_domain(m, nbytes, rng,
                                              interpreted_kernels):
    """The kernel path (interpret) scales packed bytes by shift-and-add:
    every coefficient against `MUL_TABLE` in one call when M = 256, at
    widths that are no multiple of a word, a lane or a block, and as one
    row (31 pad rows of its row block). It returns an (M, nbytes) uint8
    device array."""
    coeffs = (np.arange(256, dtype=np.uint8) if m == 256
              else rng.integers(0, 256, size=m, dtype=np.uint8))
    data = rng.integers(0, 256, size=(m, nbytes), dtype=np.uint8)
    got = ops.gf256_scale_batch(coeffs, data)
    assert isinstance(got, jax.Array)
    assert got.shape == (m, nbytes) and got.dtype == jnp.uint8
    assert np.array_equal(np.asarray(got),
                          gf256.MUL_TABLE[coeffs[:, None], data])


@pytest.mark.parametrize("m,nbytes", [
    (3, gf256_scale.BLOCK_W + 100),          # a partial second block
    (40, 2 * gf256_scale.BLOCK_W)])          # two row blocks, two blocks
def test_gf256_scale_rows_across_blocks(m, nbytes, rng):
    """Rows longer than a block run over several grid steps, and every
    step reads the masks of its own row block."""
    coeffs = rng.integers(0, 256, size=m, dtype=np.uint8)
    data = rng.integers(0, 256, size=(m, nbytes), dtype=np.uint8)
    got = gf256_scale.gf256_scale_rows(coeffs, data, interpret=True)
    assert np.array_equal(np.asarray(got),
                          gf256.MUL_TABLE[coeffs[:, None], data])


# ------------------------------------------------------------- stage_rows
@pytest.mark.parametrize("m,nbytes", [(1, 64), (1, 7), (5, 1001), (48, 256)])
def test_stage_rows_paths(m, nbytes, rng):
    """Rows cut out of wider codewords (views, not copies) stage to the
    same (M, nbytes) bytes on both paths: a host array on the numpy
    path, a device array where the kernels run, whose premultiply then
    reads the same bytes as it would from the host."""
    cws = rng.integers(0, 256, size=(m, 3, nbytes), dtype=np.uint8)
    rows = [cw[i % 3] for i, cw in enumerate(cws)]
    want = np.stack([r.copy() for r in rows])
    got_ref = ops.stage_rows(rows)
    assert isinstance(got_ref, np.ndarray)
    assert np.array_equal(got_ref, want)
    coeffs = rng.integers(0, 256, size=m, dtype=np.uint8)
    with interpreted():
        got = ops.stage_rows(rows)
        assert isinstance(got, jax.Array)
        assert got.shape == (m, nbytes) and got.dtype == jnp.uint8
        assert np.array_equal(np.asarray(got), want)
        assert np.array_equal(np.asarray(ops.gf256_scale_batch(coeffs, got)),
                              gf256.MUL_TABLE[coeffs[:, None], want])


def test_gf256_scale_batch_zero_and_one_coeffs(rng):
    data = rng.integers(0, 256, size=(3, 64), dtype=np.uint8)
    coeffs = np.array([0, 1, 255], dtype=np.uint8)
    out = np.asarray(ops.gf256_scale_batch(coeffs, data))
    assert not out[0].any()
    assert np.array_equal(out[1], data[1])


# (arguments, kernels called) of each entry point of `ops`
_CODE = RSCode(6, 3)
_DATA = np.random.default_rng(1).integers(0, 256, size=(3, 64),
                                          dtype=np.uint8)
ENTRY_POINTS = {
    "gf256_scale_batch": ((np.array([1, 2, 3], dtype=np.uint8), _DATA),
                          {"scale"}),
    "xor_reduce_segments": ((_DATA, np.array([[0, 1], [2, -1]])), {"fold"}),
    "gf256_matmul": ((np.array([[1, 2, 3], [4, 5, 6]], dtype=np.uint8),
                      _DATA), {"scale", "fold"}),
    "rs_encode": ((_CODE.parity_coeffs(), _DATA), {"scale", "fold"}),
    "rs_reconstruct": ((_CODE.repair_coeffs((0,), (1, 2, 3)), _DATA),
                       {"scale", "fold"}),
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_entry_point_runs_compiled_kernels_on_tpu(entry, monkeypatch):
    """Where the platform rule answers "TPU", every entry point of `ops`
    calls its kernels compiled (`interpret=False`) and never a numpy
    reference, and still answers with the right bytes."""
    monkeypatch.setattr(ops, "_pallas_interpret", lambda: False)  # "TPU"
    calls = []

    def compiled(name, kernel):
        def run(*args, interpret):
            calls.append((name, interpret))
            return kernel(*args, interpret=True)   # no chip here
        return run

    def refuse(*args, **kwargs):
        raise AssertionError("a numpy reference ran on TPU")

    monkeypatch.setattr(ops, "gf256_scale_rows", compiled(
        "scale", gf256_scale.gf256_scale_rows))
    monkeypatch.setattr(ops, "xor_reduce_groups_words", compiled(
        "fold", xor_reduce.xor_reduce_groups_words))
    monkeypatch.setattr(ref, "gf256_scale_batch_np", refuse)
    monkeypatch.setattr(ref, "xor_reduce_segments_np", refuse)
    args, kernels = ENTRY_POINTS[entry]
    got = getattr(ops, entry)(*args)
    assert isinstance(got, jax.Array)
    assert {name for name, _ in calls} == kernels
    assert all(interpret is False for _, interpret in calls)
    if entry == "gf256_scale_batch":
        want = gf256.MUL_TABLE[args[0][:, None], args[1]]
    elif entry == "xor_reduce_segments":
        want = np.stack([_DATA[0] ^ _DATA[1], _DATA[2]])
    else:
        want = gf256.gf_matmul_np(*args)
    assert np.array_equal(np.asarray(got), want)


# ---------------------------------------------------- xor_reduce_segments
@pytest.mark.parametrize("nbytes", [4, 96, 1000])
def test_xor_reduce_segments_paths(nbytes, rng):
    """Segment XOR-fold: ragged groups (-1 padded), both paths, vs a
    plain python fold."""
    chunks = rng.integers(0, 256, size=(7, nbytes), dtype=np.uint8)
    groups = np.array([
        [0, 1, 2, -1],
        [3, -1, -1, -1],
        [4, 5, -1, -1],
        [6, 2, 0, 1],     # rows may repeat across groups
    ])
    want = np.stack([
        np.bitwise_xor.reduce(chunks[[r for r in g if r >= 0]], axis=0)
        for g in groups
    ])
    got_ref = np.asarray(ops.xor_reduce_segments(chunks, groups))
    with interpreted():
        got_kernel = np.asarray(ops.xor_reduce_segments(chunks, groups))
    assert np.array_equal(got_ref, want)
    assert np.array_equal(got_kernel, want)


def test_xor_reduce_segments_empty(rng):
    chunks = rng.integers(0, 256, size=(3, 16), dtype=np.uint8)
    out = np.asarray(ops.xor_reduce_segments(
        chunks, np.zeros((0, 2), dtype=np.int64)))
    assert out.shape == (0, 16)


# ------------------------------------------------------ batched Gauss-Jordan
def test_gf_mat_inv_batch_matches_scalar(rng):
    for n in (2, 3, 4, 6):
        mats = []
        while len(mats) < 8:
            m = rng.integers(0, 256, size=(n, n)).astype(np.uint8)
            try:
                gf256.gf_mat_inv(m)
            except np.linalg.LinAlgError:
                continue
            mats.append(m)
        batch = gf256.gf_mat_inv_batch(np.stack(mats))
        for i, m in enumerate(mats):
            assert np.array_equal(batch[i], gf256.gf_mat_inv(m))


def test_gf_mat_inv_batch_singular_raises():
    good = np.eye(3, dtype=np.uint8)
    bad = np.zeros((3, 3), dtype=np.uint8)   # singular member
    with pytest.raises(np.linalg.LinAlgError):
        gf256.gf_mat_inv_batch(np.stack([good, bad]))


def test_gf_inv_np_vectorized():
    a = np.arange(1, 256, dtype=np.uint8)
    inv = gf256.gf_inv_np(a)
    assert all(int(inv[i]) == gf256.gf_inv(int(a[i]))
               for i in range(0, 255, 17))
    with pytest.raises(ZeroDivisionError):
        gf256.gf_inv_np(np.array([0], dtype=np.uint8))


# ---------------------------------------------------- repair_coeffs_batch
def test_repair_coeffs_batch_matches_scalar(rng):
    """Batched coefficients equal the scalar Gauss-Jordan row for row,
    for random (failed, helper-set) draws across several codes."""
    for n, k in [(4, 2), (6, 3), (7, 4), (9, 6)]:
        code = RSCode(n, k)
        failed, helpers = [], []
        for _ in range(12):
            f = int(rng.integers(n))
            hs = [x for x in range(n) if x != f]
            picks = rng.choice(len(hs), size=k, replace=False)
            failed.append(f)
            helpers.append([hs[int(i)] for i in picks])
        batch = code.repair_coeffs_batch(np.array(failed), np.array(helpers))
        assert batch.shape == (12, k) and batch.dtype == np.uint8
        for j in range(12):
            want = code.repair_coeffs((failed[j],), tuple(helpers[j]))[0]
            assert np.array_equal(batch[j], want)


def test_repair_coeffs_batch_validates():
    code = RSCode(6, 3)
    with pytest.raises(ValueError, match="helpers must be"):
        code.repair_coeffs_batch(np.array([0]), np.array([[1, 2]]))
    with pytest.raises(ValueError, match="overlap"):
        code.repair_coeffs_batch(np.array([0]), np.array([[0, 1, 2]]))
    out = code.repair_coeffs_batch(np.zeros(0, dtype=int),
                                   np.zeros((0, 3), dtype=int))
    assert out.shape == (0, 3)


def test_repair_coeffs_batch_reconstructs(rng):
    """Coefficients from the batch API actually repair bytes."""
    code = RSCode(7, 4)
    data = rng.integers(0, 256, size=(4, 128), dtype=np.uint8)
    cw = code.encode(data)
    failed = np.array([0, 2, 6])
    helpers = np.array([[1, 2, 3, 4], [0, 1, 3, 5], [0, 1, 2, 3]])
    coeffs = code.repair_coeffs_batch(failed, helpers)
    for j in range(3):
        got = np.bitwise_xor.reduce(
            gf256.MUL_TABLE[coeffs[j][:, None], cw[helpers[j]]], axis=0)
        assert np.array_equal(got, cw[failed[j]])
