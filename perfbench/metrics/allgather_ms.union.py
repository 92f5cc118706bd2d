"""Device time of the all-gather ops (the butterfly's up half) per union
allreduce call, in ms, averaged over the chips (device trace)."""


def read(ctx):
    t, f = ctx.trace, ctx.facts
    if t is None or "calls" not in f or "allgather" not in t["collective_s"]:
        return None
    return 1e3 * t["collective_s"]["allgather"] / f["calls"]
