"""Pallas TPU kernel: XOR-reduce groups of chunks, one result per group.

The PPR / BMFRepair aggregation step: helper partial results (already Galois-
premultiplied, c_i (*) B_i) combine by plain XOR. Operates on raw uint32
words (XOR is byte-order agnostic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_W = 1024


def _group_kernel(x_ref, out_ref, *, k: int):
    acc = x_ref[0, 0, :]
    for i in range(1, k):
        acc = acc ^ x_ref[0, i, :]
    out_ref[0, 0, :] = acc


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def xor_reduce_groups_words(
    words: jax.Array, *, block_w: int = DEFAULT_BLOCK_W, interpret: bool = True
) -> jax.Array:
    """(G, K, W) uint32 -> (G, W) uint32: XOR over axis 1, per group.

    The segment-XOR of the batched data plane: group g holds the (padded)
    payloads arriving at one (case, destination) in a round. A running
    XOR over the K members of a (1, K, block_w) block, driven over a
    (G, W/block_w) grid — one `pallas_call` folds every fan-in group of a
    whole round batch.

    The output carries a unit middle axis, (G, 1, W), so the last two
    dims of its (1, 1, block_w) block equal or tile the array's: TPU
    lowering rejects a (1, block_w) block over a (G, W) array.
    """
    g, k, w = words.shape
    w_pad = -w % block_w
    if w_pad:
        words = jnp.pad(words, ((0, 0), (0, 0), (0, w_pad)))
    wp = words.shape[-1]
    out = pl.pallas_call(
        functools.partial(_group_kernel, k=k),
        grid=(g, wp // block_w),
        in_specs=[pl.BlockSpec((1, k, block_w), lambda r, t: (r, 0, t))],
        out_specs=pl.BlockSpec((1, 1, block_w), lambda r, t: (r, 0, t)),
        out_shape=jax.ShapeDtypeStruct((g, 1, wp), jnp.uint32),
        interpret=interpret,
    )(words)
    return out[:, 0, :w]
