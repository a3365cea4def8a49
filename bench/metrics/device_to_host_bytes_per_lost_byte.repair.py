"""Bytes the data plane copies back from the device, per lost byte.

The `bytes` counter of the program's span `repro.dataplane.d2h`
(`repro.spans`, which records only while the window is traced): the
`nbytes` of every GF(256) result that comes back as a `jax.Array` and is
copied to the host, over the lost-block bytes the window rebuilt. None
where the program has no such span.
"""


def read(ctx):
    try:
        import repro.spans as spans
    except ImportError:
        return None
    totals = spans.totals()
    if not totals.get("repro.dataplane.batch", {}).get("count") \
            or not ctx.lost_bytes:
        return None
    return totals.get("repro.dataplane.d2h", {}).get("bytes", 0) \
        / ctx.lost_bytes
