"""Fixed cost of one round of the data plane's round loop, in ms.

Total time of the program's span `repro.dataplane.round` (`repro.spans`,
which records only while the window is traced) over its count: the host
index work, the payload's take, the fold call and the dispatch of the
store's update, once per round of every call. A call runs as many rounds
as its batch's longest plan, so a code or a stripe with more rounds pays
it more often per lost byte. None where the program has no such span.
"""


def read(ctx):
    try:
        import repro.spans as spans
    except ImportError:
        return None
    totals = spans.totals()
    rounds = totals.get("repro.dataplane.round", {})
    if not totals.get("repro.dataplane.batch", {}).get("count") \
            or not ctx.lost_bytes or not rounds.get("count"):
        return None
    return rounds["total_s"] * 1e3 / rounds["count"]
