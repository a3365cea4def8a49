"""Time the data plane spends copying GF(256) results back to the host,
in ms per lost MiB.

Self time of the program's span `repro.dataplane.d2h` (`repro.spans`,
which records only while the window is traced): the `np.asarray` of
every premultiply and fold result, over the lost-block MiB the window
rebuilt. None where the program has no such span.
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
    seconds = totals.get("repro.dataplane.d2h", {}).get("self_s", 0.0)
    return seconds * 1e3 / (ctx.lost_bytes / 2**20)
