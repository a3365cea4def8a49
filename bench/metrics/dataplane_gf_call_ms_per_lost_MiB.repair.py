"""Host time inside the data plane's two GF(256) calls, in ms per lost
MiB.

Total time of the program's spans `repro.dataplane.premultiply` and
`repro.dataplane.fold` (`repro.spans`, which records only while the
window is traced), over the lost-block MiB the window rebuilt. Under the
benchmark's seams each call waits for its result inside the span, so
this holds the device time too. None where the program has no such
spans.
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
    seconds = sum(totals.get(f"repro.dataplane.{step}", {}).get("total_s", 0.0)
                  for step in ("premultiply", "fold"))
    return seconds * 1e3 / (ctx.lost_bytes / 2**20)
