"""Least bytes and least times of the work, from the inputs alone (edge
lists, vertex and row counts, row width), never from the program's padded
shapes or kernel tiles: a layout that drops padding cannot push a share
past 100%."""
from __future__ import annotations

import numpy as np


def spmv_needed_bytes(edges: np.ndarray) -> int:
    """Bytes one PageRank round must move through HBM at the least: one
    int32 column per stored edge, one 4-byte input entry per vertex read
    and one 4-byte output per row written.  (The edge weight is the
    source's 1/outdeg, which folds into the input vector.)"""
    distinct = [np.count_nonzero(np.bincount(ends)) for ends in edges.T]
    return 4 * (len(edges) + sum(distinct))


def union_least_time(own_rows, union_rows: int, width: int,
                     peaks: dict):
    """Least time of one union allreduce and which bound sets it.

    A row is a uint32 index and ``width`` float32 values.  Each node has
    to receive every union row it does not hold, over its inter-chip links
    (``ici``), and has to read its own rows and write the whole union in
    its HBM (``hbm``).  Returns ``(seconds, "ici" | "hbm")``."""
    row = 4 + 4 * width
    ici = max(union_rows - o for o in own_rows) * row / peaks["ici_bytes_per_s"]
    hbm = max(o + union_rows for o in own_rows) * row / peaks["hbm_bytes_per_s"]
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")
