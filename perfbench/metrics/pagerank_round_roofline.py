"""The whole round's share: least time of the SpMV's needed bytes at HBM
peak plus the reduce's least bytes at inter-chip peak (0 on one chip),
over the traced window's wall time per round (host clock)."""


def read(ctx):
    f = ctx.facts
    if "needed_bytes_per_round" not in f:
        return None
    least = (f["needed_bytes_per_round"] / ctx.peaks["hbm_bytes_per_s"]
             + f["reduce_least_bytes_per_round"] / ctx.peaks["ici_bytes_per_s"])
    return 100.0 * least / (f["window_s"] / f["rounds"])
