"""Pallas TPU kernel: GF(256) matrix multiply over bit-sliced chunks.

Computes out[o, :] = XOR_i ( C[o, i] (*) data[i, :] ) where (*) is GF(256)
multiplication, in the bit-plane domain (see repro/ec/bitplane.py):

  out_plane[o, bi, w] = XOR_{i, bj} plane[i, bj, w] & mask[o, i, bi, bj]

masks are pre-expanded uint32 {0, 0xFFFFFFFF} AND-masks of the 8x8 GF(2)
bit-matrix of each coefficient, so the inner loop is branch-free AND/XOR on
(8, BLOCK_W) uint32 tiles — pure VPU work, no gathers (TPU has no byte
shuffle; this is the TPU-native adaptation of ISA-L's PSHUFB method).

VMEM budget per grid step (BLOCK_W=512, k=16):
  planes (k, 8, 512) u32 = 256 KiB, masks (1, k, 8, 8) = 4 KiB,
  out (1, 8, 512) = 16 KiB  -> well under 16 MiB VMEM.
Lane dim 512 = 4x128 lanes; sublane dim 8 matches the u32 tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_W = 512


def _kernel(mask_ref, plane_ref, out_ref, *, k: int):
    acc = jnp.zeros(out_ref.shape[1:], dtype=jnp.uint32)  # (8, BW)
    for i in range(k):          # static unroll: k is small (<= 16)
        for bj in range(8):
            d = plane_ref[i, bj, :]          # (BW,) u32
            msk = mask_ref[0, i, :, bj]      # (8,)  u32
            acc = acc ^ (d[None, :] & msk[:, None])
    out_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def gf256_matmul_planes(
    masks: jax.Array,
    planes: jax.Array,
    *,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = True,
) -> jax.Array:
    """(m,k,8,8) u32 masks x (k,8,W) u32 planes -> (m,8,W) u32 planes.

    W is padded to a multiple of block_w internally.
    """
    m, k = masks.shape[0], masks.shape[1]
    kk, eight, w = planes.shape
    assert kk == k and eight == 8, (masks.shape, planes.shape)
    w_pad = -w % block_w
    if w_pad:
        planes = jnp.pad(planes, ((0, 0), (0, 0), (0, w_pad)))
    wp = planes.shape[-1]
    grid = (m, wp // block_w)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, k, 8, 8), lambda o, t: (o, 0, 0, 0)),
            pl.BlockSpec((k, 8, block_w), lambda o, t: (0, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, 8, block_w), lambda o, t: (o, 0, t)),
        out_shape=jax.ShapeDtypeStruct((m, 8, wp), jnp.uint32),
        interpret=interpret,
    )(masks, planes)
    return out[:, :, :w]
