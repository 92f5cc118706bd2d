"""Device busy time outside collectives per union allreduce call, in ms,
averaged over the chips (device trace): bucketing, merge and compaction,
whichever merge implements them."""


def read(ctx):
    t, f = ctx.trace, ctx.facts
    if t is None or "calls" not in f or t["other_s"] <= 0:
        return None
    return 1e3 * t["other_s"] / f["calls"]
