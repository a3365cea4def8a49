"""GF(256) entry points of `repro.kernels.ops` on both of their paths —
the numpy references and the Pallas kernels (interpret mode) — vs the
pure-jnp/numpy oracles."""
import contextlib

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import interpreted
from repro.ec import gf256
from repro.kernels import ops, ref

PATHS = {"ref": contextlib.nullcontext, "kernel": interpreted}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("m,k", [(1, 2), (2, 3), (3, 4), (2, 6), (4, 8), (1, 16)])
@pytest.mark.parametrize("nbytes", [32, 100, 1024, 4096])
def test_gf256_matmul_sweep(m, k, nbytes, path, rng):
    coeff = rng.integers(0, 256, size=(m, k), dtype=np.uint8)
    data = rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8)
    want = gf256.gf_matmul_np(coeff, data)
    with PATHS[path]():
        got = np.asarray(ops.gf256_matmul(coeff, jnp.asarray(data)))
    assert np.array_equal(got, want)
    # independent byte-domain oracle agrees too
    got_ref = np.asarray(ref.gf256_matmul_bytes_ref(coeff, jnp.asarray(data)))
    assert np.array_equal(got_ref, want)


@pytest.mark.parametrize("k", [2, 3, 5, 9])
@pytest.mark.parametrize("nbytes", [4, 64, 999, 2048])
def test_xor_reduce_sweep(k, nbytes, rng, interpreted_kernels):
    x = rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8)
    want = x[0].copy()
    for i in range(1, k):
        want ^= x[i]
    got = np.asarray(ops.xor_reduce_segments(jnp.asarray(x),
                                             np.arange(k)[None])[0])
    assert np.array_equal(got, want)


def test_rs_encode_reconstruct_via_kernels(rng, interpreted_kernels):
    from repro.ec.rs import RSCode
    for (n, k) in [(4, 2), (6, 3), (7, 4), (6, 4)]:
        code = RSCode(n, k)
        data = rng.integers(0, 256, size=(k, 512), dtype=np.uint8)
        parity = np.asarray(
            ops.rs_encode(code.parity_coeffs(), jnp.asarray(data)))
        cw = np.concatenate([data, parity])
        failed = list(rng.choice(n, size=n - k, replace=False))
        helpers = [i for i in range(n) if i not in failed][:k]
        rec = np.asarray(ops.rs_reconstruct(
            code.repair_coeffs(tuple(failed), tuple(helpers)),
            jnp.asarray(cw[helpers])))
        assert np.array_equal(rec, cw[failed])
