"""Host time of the data plane's own numpy work, in ms per lost MiB.

Self time of the program's spans `repro.dataplane.prepare`, `stage`,
`scatter`, `gather`, `accumulate` and `verify` (`repro.spans`, which
records only while the window is traced), over the lost-block MiB the
window rebuilt. None where the program has no such spans.
"""
STEPS = ("prepare", "stage", "scatter", "gather", "accumulate", "verify")


def read(ctx):
    try:
        import repro.spans as spans
    except ImportError:
        return None
    totals = spans.totals()
    if not totals.get("repro.dataplane.batch", {}).get("count") \
            or not ctx.lost_bytes:
        return None
    seconds = sum(totals.get(f"repro.dataplane.{step}", {}).get("self_s", 0.0)
                  for step in STEPS)
    return seconds * 1e3 / (ctx.lost_bytes / 2**20)
