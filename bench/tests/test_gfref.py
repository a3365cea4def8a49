"""The benchmark's own GF(2^8) arithmetic against shift-and-add products."""
import numpy as np
import pytest

import gfref


def test_table_matches_shift_and_add():
    rng = np.random.default_rng(0)
    for a, b in rng.integers(0, 256, size=(500, 2)):
        assert gfref.MUL[a, b] == gfref.mul_slow(int(a), int(b))
    assert all(gfref.MUL[a, gfref.INV[a]] == 1 for a in range(1, 256))


@pytest.mark.parametrize("n,k", [(9, 6), (14, 10)])
def test_generator_is_systematic_and_mds(n, k):
    g = gfref.generator(n, k)
    assert np.array_equal(g[:k], np.eye(k, dtype=np.uint8))
    rng = np.random.default_rng(n)
    for _ in range(20):
        rows = sorted(rng.choice(n, size=k, replace=False))
        gfref.mat_inv(g[rows])          # any k rows are invertible


@pytest.mark.parametrize("n,k", [(9, 6), (14, 10)])
def test_device_encode_and_every_single_repair(n, k):
    import jax

    cw = np.asarray(gfref.encode_device(jax.random.key(1), 2, n, k, 96))
    for s in range(2):
        assert np.array_equal(cw[s], gfref.encode_np(cw[s, :k], n))
    for f in range(n):
        helpers = [x for x in range(n) if x != f][:k]
        row = gfref.repair_row(n, k, f, helpers)
        assert np.array_equal(gfref.combine(row, cw[0][helpers]), cw[0][f])
