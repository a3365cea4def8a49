"""Bytes the data plane hands from host memory to the two GF(256) steps,
per lost byte.

Counted at the seams `ops.gf256_scale_batch` and `ops.xor_reduce_segments`:
every argument that is not a `jax.Array` lives on the host and has to be
copied to the device for the kernels, so its `nbytes` counts; an argument
already on the device counts nothing. Summed over the window's calls and
divided by the lost-block bytes the window rebuilt: an exact count of the
host-to-device traffic that the two steps' inputs cause.
"""


def read(ctx):
    if not ctx.calls or not ctx.lost_bytes:
        return None
    return sum(c["h2d_bytes"] for c in ctx.calls) / ctx.lost_bytes
