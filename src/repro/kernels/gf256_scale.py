"""Pallas TPU kernel: scale each row of bytes by its own GF(256) coefficient.

The batched data plane's premultiply, `out[r, :] = c[r] (*) data[r, :]`,
computed on the bytes as they are, with no bit planes. A multiply by c in
GF(2^8) (`gf256.PRIM_POLY` = 0x11D) is shift-and-add over the bits of c:

  acc = 0; x = byte
  for b in 0..7:
      if bit b of c: acc ^= x
      x = (x << 1) ^ (0x1D if x & 0x80 else 0)      # x * 2 ("xtime")

Four bytes share a uint32 word, and `xtime` keeps them apart: with
`hi = (x >> 7) & 0x01010101`, the top bit of each byte,
`((x & 0x7F7F7F7F) << 1) ^ hi * 0x1D` doubles every byte of the word at
once (`hi * 0x1D` puts 0x1D in each byte whose top bit was set, with no
carry between bytes). The `if` becomes an AND with a mask that holds 0xFF
in each byte whose row has bit b set. Pure VPU work, AND/XOR/shift and a
multiply by a constant: no tables, no gathers.

Layout. The kernel reads and writes the `(M, nbytes)` uint8 array itself,
in `(32, BLOCK_W)` blocks: 32 rows because a uint8 vreg holds a
`(32, 128)` tile, whose bitcast to a `(8, 128)` uint32 tile is free.
Which 4 bytes share a word is the hardware's packing; the masks are made
as `(32, 128)` uint8 tiles too and go through the same bitcast, so each
mask byte lands beside the data byte of its own row, whatever the packing.
Inside a block a loop walks `(32, SUB_W)` slices, so every step works on
`SUB_W / 128` vregs held in registers.

VMEM budget per grid step (BLOCK_W = 65536): the data block
`(32, 65536)` u8 = 2 MiB, in and out each double-buffered = 8 MiB, and
the masks `(8, 32, 128)` u8 = 32 KiB (x2) -> under the 16 MiB of scoped
VMEM. A 1 MiB row takes 16 blocks; a batch of M rows, ceil(M/32) x 16
grid steps. Rows narrower than a block take one block of their width
rounded up to SUB_W; rows and bytes beyond the array are never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_W = 65536        # bytes of each row in one grid step
SUB_W = 512            # bytes of each row in one step of the inner loop
ROWS = 32              # rows of a uint8 (32, 128) tile


def _xtime(x):
    """Every byte of the uint32 words `x` times 2 in GF(2^8)."""
    hi = jax.lax.shift_right_logical(x, jnp.uint32(7)) & jnp.uint32(0x01010101)
    return (((x & jnp.uint32(0x7F7F7F7F)) << jnp.uint32(1))
            ^ (hi * jnp.uint32(0x1D)))


def _kernel(mask_ref, data_ref, out_ref):
    reps = SUB_W // 128
    masks = [jnp.tile(pltpu.bitcast(mask_ref[b], jnp.uint32), (1, reps))
             for b in range(8)]                              # (8, SUB_W) u32

    def step(i, carry):
        cols = pl.ds(pl.multiple_of(i * SUB_W, SUB_W), SUB_W)
        x = pltpu.bitcast(data_ref[:, cols], jnp.uint32)     # (8, SUB_W)
        acc = x & masks[0]
        for b in range(1, 8):
            x = _xtime(x)
            acc = acc ^ (x & masks[b])
        out_ref[:, cols] = pltpu.bitcast(acc, jnp.uint8)
        return carry

    jax.lax.fori_loop(0, data_ref.shape[1] // SUB_W, step, 0)


def _bit_masks(coeffs: jax.Array, rows: int) -> jax.Array:
    """(M,) uint8 -> (8, rows, 128) uint8: 0xFF in plane b, row r where
    bit b of coeffs[r] is set; rows beyond M read 0."""
    c = jnp.pad(coeffs.astype(jnp.uint8), (0, rows - coeffs.shape[0]))
    bits = (c[None, :] >> jnp.arange(8, dtype=jnp.uint8)[:, None]) & 1
    return jnp.broadcast_to((bits * jnp.uint8(0xFF))[:, :, None],
                            (8, rows, 128))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gf256_scale_rows(coeffs, data, *, interpret: bool = True) -> jax.Array:
    """(M,) uint8 coeffs x (M, nbytes) uint8 -> (M, nbytes) uint8: row r
    scaled by coeffs[r], one `pallas_call` over a
    (ceil(M/32), ceil(nbytes/BLOCK_W)) grid."""
    m, nbytes = data.shape
    assert coeffs.shape == (m,), (coeffs.shape, data.shape)
    row_blocks = pl.cdiv(m, ROWS)
    block_w = min(BLOCK_W, pl.cdiv(nbytes, SUB_W) * SUB_W)
    return pl.pallas_call(
        _kernel,
        grid=(row_blocks, pl.cdiv(nbytes, block_w)),
        in_specs=[
            pl.BlockSpec((8, ROWS, 128), lambda r, t: (0, r, 0)),
            pl.BlockSpec((ROWS, block_w), lambda r, t: (r, t)),
        ],
        out_specs=pl.BlockSpec((ROWS, block_w), lambda r, t: (r, t)),
        out_shape=jax.ShapeDtypeStruct((m, nbytes), jnp.uint8),
        interpret=interpret,
    )(_bit_masks(coeffs, row_blocks * ROWS), data)
