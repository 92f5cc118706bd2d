"""Least time of a PageRank round's needed bytes at HBM peak, over the
SpMV's device time per round: the device busy time of the engine's
dispatches outside collectives, over the rounds traced.  Needed bytes come
from the edge list (``perfbench.roofline.spmv_needed_bytes``)."""


def read(ctx):
    f, t = ctx.facts, ctx.trace
    if t is None or "needed_bytes_per_round" not in f or t["other_s"] <= 0:
        return None
    least = f["needed_bytes_per_round"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (t["other_s"] / f["rounds"])
