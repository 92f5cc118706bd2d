"""Needed bytes and least times come from the inputs, not from layouts."""
import numpy as np
import pytest

from perfbench import gen, roofline
from perfbench.peaks import UnknownDevice, peaks_for


def _needed_bytes_from_ell(cols):
    """The needed bytes read back from padded ELL column tables (``cols <
    0`` are empty slots): stored edges, distinct columns, rows written."""
    live = cols >= 0
    rows = int(live.any(axis=1).sum())
    return 4 * (int(live.sum()) + len(np.unique(cols[live])) + rows)


def _ell(edges, n_rows_pad, k_pad):
    """Padded ELL column tables of ``edges`` (dst rows, src columns) with
    ``n_rows_pad`` rows and ``k_pad`` slots, plus unused padding rows."""
    dst, src = edges[:, 1], edges[:, 0]
    rows = np.unique(dst)
    pos = np.searchsorted(rows, dst)
    counts = np.bincount(pos, minlength=len(rows))
    k = max(int(counts.max()), k_pad)
    cols = np.full((max(n_rows_pad, len(rows)), k), -1, np.int64)
    order = np.argsort(pos, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(len(pos)) - starts[pos[order]]
    cols[pos[order], slot] = src[order]
    return cols


@pytest.mark.parametrize("rows_pad,k_pad", [(0, 1), (0, 64), (700, 5000)])
def test_needed_bytes_same_from_edges_and_any_ell(rows_pad, k_pad):
    edges = gen.powerlaw_graph(512, 16 * 512, alpha=2.2, seed=3)
    want = roofline.spmv_needed_bytes(edges)
    assert want == 4 * (len(edges) + len(np.unique(edges[:, 0]))
                        + len(np.unique(edges[:, 1])))
    assert _needed_bytes_from_ell(_ell(edges, rows_pad, k_pad)) == want


def test_needed_bytes_ignore_the_padding_factor():
    """Padding to the widest row multiplies the table, not the bytes."""
    edges = gen.powerlaw_graph(2048, 16 * 2048, alpha=2.2, seed=0)
    cols = _ell(edges, 0, 1)
    assert cols.size * 4 > 20 * roofline.spmv_needed_bytes(edges)


def test_union_least_time_takes_the_larger_bound():
    peaks = peaks_for("TPU v5 lite")
    t, bound = roofline.union_least_time([23000] * 4, 55000, 1024, peaks)
    assert bound == "ici"
    assert t == pytest.approx((55000 - 23000) * 4100 / 200e9)
    t, bound = roofline.union_least_time([1000] * 4, 1000, 1024, peaks)
    assert bound == "hbm"
    assert t == pytest.approx(2000 * 4100 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v4")
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
