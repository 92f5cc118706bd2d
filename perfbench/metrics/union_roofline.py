"""Least time of a union allreduce over the mean call time of the traced
window (host clock).  The least time is the larger of the inter-chip bound
(the union rows a node lacks, received at ICI peak) and the HBM bound (own
rows read plus the union written), averaged over the pool's steps
(``perfbench.roofline.union_least_time``)."""

from perfbench.roofline import union_least_time


def read(ctx):
    f = ctx.facts
    if "calls" not in f:
        return None
    least = [union_least_time(o, u, f["width"], ctx.peaks)[0]
             for o, u in zip(f["own_rows"], f["union_rows"])]
    return 100.0 * (sum(least) / len(least)) / (f["window_s"] / f["calls"])
