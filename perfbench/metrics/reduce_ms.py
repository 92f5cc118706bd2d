"""Window seconds over union allreduce calls completed, in ms (host
clock, the window spans all calls)."""


def read(ctx):
    f = ctx.facts
    if "calls" not in f:
        return None
    return 1e3 * f["window_s"] / f["calls"]
