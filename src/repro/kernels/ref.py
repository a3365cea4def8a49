"""Oracles for the GF(256) kernels of `repro.kernels.ops`.

* `gf256_scale_batch_np` and `xor_reduce_segments_np` — the numpy
  references `ops` runs off TPU in place of the two Pallas kernels;
* `gf256_matmul_bytes_ref` — an independent byte-domain formulation in
  jnp (one 256-entry `MUL_TABLE` row per coefficient, XOR-accumulated),
  which the tests hold next to `ops.gf256_matmul`.

Tests cross-check all of them against the numpy peasant-multiply ground
truth in repro/ec/gf256.py.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.ec import gf256


def gf256_matmul_bytes_ref(coeff: np.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """(m,k) static uint8 coeffs x (k, nbytes) uint8 -> (m, nbytes) uint8.

    Byte-domain oracle: per-coefficient 256-entry table lookup (jnp.take)
    XOR-accumulated. Coefficients must be concrete (numpy) — they select
    which table row to use.
    """
    coeff = np.asarray(coeff, dtype=np.uint8)
    m, k = coeff.shape
    assert data.shape[0] == k
    outs = []
    for o in range(m):
        acc = jnp.zeros(data.shape[1:], dtype=jnp.uint8)
        for i in range(k):
            c = int(coeff[o, i])
            if c == 0:
                continue
            if c == 1:
                acc = acc ^ data[i]
            else:
                row = jnp.asarray(gf256.MUL_TABLE[c])  # (256,)
                acc = acc ^ jnp.take(row, data[i].astype(jnp.int32))
        outs.append(acc)
    return jnp.stack(outs)


def gf256_scale_batch_np(coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(M,) uint8 coeffs x (M, nbytes) uint8 -> (M, nbytes): per-row scale.

    Numpy oracle for the batched premultiply (`kernels.ops.gf256_scale_batch`):
    one dense MUL_TABLE gather covers the whole batch. This is the
    non-interpret ref path the batched data plane runs off-TPU.
    """
    coeffs = np.asarray(coeffs, dtype=np.uint8).reshape(-1)
    data = np.asarray(data, dtype=np.uint8)
    assert data.shape[0] == coeffs.shape[0], (coeffs.shape, data.shape)
    return gf256.MUL_TABLE[coeffs[:, None], data]


def xor_reduce_segments_np(chunks: np.ndarray, groups: np.ndarray) -> np.ndarray:
    """(T, nbytes) chunks + (G, Kmax) row-index groups (-1 padded) ->
    (G, nbytes): XOR of each group's member rows (numpy oracle)."""
    chunks = np.asarray(chunks, dtype=np.uint8)
    groups = np.asarray(groups, dtype=np.int64)
    if groups.size == 0:
        return np.zeros((groups.shape[0], chunks.shape[-1]), dtype=np.uint8)
    rows = chunks[np.maximum(groups, 0)]          # (G, K, nbytes) copy
    rows[groups < 0] = 0
    return np.bitwise_xor.reduce(rows, axis=1)
