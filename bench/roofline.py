"""Algorithmic bytes of the data plane's two GF(256) steps.

Each count is the least traffic the step must make in device memory,
worked out from the call's arguments alone: every input chunk read once
and every output chunk written once. It does not follow what today's
implementation touches (bit-plane packing, a zero row, a gathered copy),
so a change that drops such a pass reads faster, never as less work.
"""
from __future__ import annotations


def premultiply_bytes(rows: int, nbytes: int) -> int:
    """`gf256_scale_batch`: read M chunks, write M scaled chunks."""
    return 2 * rows * nbytes


def fold_bytes(rows: int, groups: int, nbytes: int) -> int:
    """`xor_reduce_segments`: read T payload rows, write G folded rows."""
    return (rows + groups) * nbytes


def share(alg_bytes: int, device_s: float, peak_bytes_per_s: float):
    """Roofline share in %: the least time at the HBM peak over the time
    the step's device ops took. None where the trace showed no op."""
    if device_s <= 0:
        return None
    return 100.0 * alg_bytes / peak_bytes_per_s / device_s


def step_share(ctx, op: str):
    """Roofline share over every call of `op` the traced window recorded:
    each call's span is `bench.<op>#<index in ctx.calls>`."""
    if ctx.trace is None or ctx.peak is None:
        return None
    alg = dev = 0.0
    for i, c in enumerate(ctx.calls):
        if c["op"] == op:
            alg += c["alg_bytes"]
            dev += ctx.trace.span_device_s.get(f"bench.{op}#{i}", 0.0)
    return share(alg, dev, ctx.peak["hbm_bytes_per_s"])
