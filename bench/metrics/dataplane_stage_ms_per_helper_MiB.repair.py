"""Host time the data plane spends staging helper chunks, in ms per MiB
staged.

Self time of the program's span `repro.dataplane.stage` (`repro.spans`,
which records only while the window is traced) over its `helper_bytes`
counter: the helper chunks collected into one host array for the
premultiply, k per lost block. Per byte copied, so codes of different
fan-in read on one scale. None where the program has no such span or
counter.
"""


def read(ctx):
    try:
        import repro.spans as spans
    except ImportError:
        return None
    totals = spans.totals()
    stage = totals.get("repro.dataplane.stage", {})
    if not totals.get("repro.dataplane.batch", {}).get("count") \
            or not ctx.lost_bytes or not stage.get("helper_bytes"):
        return None
    return stage["self_s"] * 1e3 / (stage["helper_bytes"] / 2**20)
