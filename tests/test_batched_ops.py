"""Batched data-plane primitives: grid-driven kernel entry points
(`kernels.ops.gf256_scale_batch` / `xor_reduce_segments`), the lockstep
GF(256) Gauss-Jordan, and `RSCode.repair_coeffs_batch`.

Separate from tests/test_kernels.py and tests/test_rs.py on purpose:
those modules skip entirely without hypothesis, while everything here is
deterministic and must run on the bare-numpy tier-1 environment too.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ec import gf256
from repro.ec.rs import RSCode
from repro.kernels import ops


# ------------------------------------------------------- gf256_scale_batch
@pytest.mark.parametrize("m,nbytes", [(1, 32), (5, 100), (16, 1024)])
def test_gf256_scale_batch_paths(m, nbytes, rng):
    """Batched per-row premultiply: numpy ref path and grid-driven kernel
    path (interpret) both equal the per-row table ground truth."""
    coeffs = rng.integers(0, 256, size=m, dtype=np.uint8)
    data = rng.integers(0, 256, size=(m, nbytes), dtype=np.uint8)
    want = np.stack([gf256.MUL_TABLE[coeffs[i], data[i]] for i in range(m)])
    got_ref = np.asarray(ops.gf256_scale_batch(coeffs, data,
                                               use_kernel=False))
    got_kernel = np.asarray(ops.gf256_scale_batch(
        coeffs, data, use_kernel=True, interpret=True))
    assert np.array_equal(got_ref, want)
    assert np.array_equal(got_kernel, want)
    # also matches m calls of the (m, k) matmul entry point
    per_row = np.concatenate([
        np.asarray(ops.gf256_matmul(coeffs[i: i + 1, None],
                                    jnp.asarray(data[i: i + 1]),
                                    use_kernel=False))
        for i in range(m)
    ])
    assert np.array_equal(per_row, want)


def test_gf256_scale_batch_zero_and_one_coeffs(rng):
    data = rng.integers(0, 256, size=(3, 64), dtype=np.uint8)
    coeffs = np.array([0, 1, 255], dtype=np.uint8)
    out = np.asarray(ops.gf256_scale_batch(coeffs, data, use_kernel=False))
    assert not out[0].any()
    assert np.array_equal(out[1], data[1])


@pytest.mark.parametrize("kwargs", [
    dict(use_kernel=False), dict(interpret=True),
    dict(use_kernel=True, interpret=True)])
@pytest.mark.parametrize("entry", ["gf256_scale_batch", "xor_reduce_segments"])
def test_batched_entry_points_refuse_ref_and_interpret_on_tpu(
        entry, kwargs, monkeypatch, rng):
    """On TPU the batched entry points run the compiled kernels only: a
    request for the numpy oracle or the interpreter there raises instead
    of quietly leaving the device."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)  # "TPU"
    data = rng.integers(0, 256, size=(2, 64), dtype=np.uint8)
    args = {"gf256_scale_batch": (np.array([1, 2], dtype=np.uint8), data),
            "xor_reduce_segments": (data, np.array([[0, 1]]))}[entry]
    with pytest.raises(ValueError, match="compiled kernels only"):
        getattr(ops, entry)(*args, **kwargs)


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
    got_ref = np.asarray(ops.xor_reduce_segments(chunks, groups,
                                                 use_kernel=False))
    got_kernel = np.asarray(ops.xor_reduce_segments(
        chunks, groups, use_kernel=True, interpret=True))
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
