"""Calibrated heterogeneous-degree autotuner with a persistent plan cache.

The paper's throughput claim (§IV) is that the optimal Sparse Allreduce
network is a nested butterfly of *heterogeneous degree decreasing with
depth*, chosen by a communication cost model.  ``core.topology.tune``
sweeps ``ordered_factorizations`` against that model — but a model is only
as good as its :class:`~repro.core.netmodel.Fabric` parameters, and nominal
specs are fiction (the paper's own testbed achieved 2 Gb/s of its rated
10 Gb/s).  This module closes the loop, in three parts (docs book chapter:
``TUNING.md``):

1. **Calibrate** — :func:`measure_stage_samples` times single butterfly
   stages (grouped ``all_to_all`` inside ``shard_map``) over a ragged
   payload x fanout sweep on the *actual* mesh, and :func:`fit_fabric`
   least-squares fits the alpha / beta / gamma terms of the extended
   alpha-beta-floor-gamma model (``netmodel.Fabric.gamma_s`` is the
   per-fanout congestion term that makes degree-vs-depth tradeoffs
   expressible).  :func:`measure_plan` times whole reduces for
   modeled-vs-measured validation.
2. **Select** — :func:`select_plan` reranks ``ordered_factorizations``
   under the calibrated fabric with the power-law ``expected_counts``
   sparsity curve, optionally confirms the top-k candidates by timed
   trial, and reports whether the paper's decreasing-degree structure
   holds (warns when it does not).
3. **Cache** — :class:`PlanCache` persists ``{mesh shape, nnz profile,
   merge mode, replication} -> degrees (+ frozen routing/staging
   metadata)`` via ``repro.checkpoint.store``, so
   ``make_train_step(dp_degrees="auto")``, ``GraphEngine`` and
   ``launch/train.py`` get cache hits instead of re-tuning; an in-process
   memo additionally dedupes ``SparseAllreduce.config`` plans so a cache
   hit performs **zero retraces** (same jitted reduce fn reused).

Entry point: :func:`resolve_degrees` (what ``degrees="auto"`` resolves
through).  Cache location: ``$REPRO_PLAN_CACHE`` or
``~/.cache/repro/plans``; ``retune=True`` (CLI ``--retune``) bypasses
reads and overwrites.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import time
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .netmodel import EC2_2013, Fabric
from .topology import ButterflyPlan, check_wire, num_prime_factors, tune

CACHE_ENV = "REPRO_PLAN_CACHE"
_KEY_VERSION = 1

# Dtypes staged through the calibration all_to_alls — the same streams the
# real union path ships per stage (uint32 index + fp32 value).  The sample
# byte accounting below derives from these itemsizes; keep them in sync.
STAGE_IDX_DTYPE = np.dtype(np.uint32)
STAGE_VAL_DTYPE = np.dtype(np.float32)


# ---------------------------------------------------------------------------
# 1. Calibration: stage microbenchmarks -> least-squares Fabric fit
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageSample:
    """One observed butterfly-stage timing.

    ``nbytes``: payload bytes per destination; ``fanout``: peers exchanged
    with (``k - 1`` for a degree-k stage); ``time_s``: wall seconds for
    the stage.
    """
    nbytes: float
    fanout: int
    time_s: float


def synth_stage_samples(fabric: Fabric, payload_bytes: Sequence[float],
                        fanouts: Sequence[int], *, serial: bool = True,
                        noise: float = 0.0, seed: int = 0
                        ) -> List[StageSample]:
    """Stage samples generated *from* a known fabric (fit-recovery tests
    and the deterministic calibration rows of ``bench_autotune``).

    ``noise`` is a relative gaussian perturbation (0 = exact model times).
    """
    rng = np.random.RandomState(seed)
    out = []
    for b in payload_bytes:
        for f in fanouts:
            t = fabric.stage_time(b, f, serial=serial)
            if noise:
                t *= max(1.0 + noise * float(rng.randn()), 0.05)
            out.append(StageSample(float(b), int(f), max(t, 1e-12)))
    return out


def fit_fabric(samples: Sequence[StageSample], *, serial: bool = True,
               name: str = "calibrated", floor_bytes: float = 0.0) -> Fabric:
    """Least-squares fit of ``Fabric(alpha_s, beta_bytes_per_s, gamma_s)``
    from stage timings.

    The stage model (``Fabric.stage_time``) is linear in
    ``(alpha, gamma, 1/beta)`` once normalized:

    * serial NIC:  ``t / f = alpha + gamma * (f - 1) + b / beta``
    * per-link:    ``t = f * alpha + gamma * (f - 1) + b / beta``

    so a single ``lstsq`` (with column scaling for conditioning) recovers
    all three terms; they are clamped to physical ranges (alpha > 0,
    gamma >= 0, beta > 0).  The packet floor is *not* fit — feed payloads
    above the suspected floor, or pass ``floor_bytes`` through explicitly.
    Needs >= 3 samples spanning >= 2 distinct payload sizes (else beta is
    unidentifiable — ValueError) and >= 2 distinct fanouts; with a single
    fanout (e.g. a prime device count, whose only stage degree is M) the
    alpha and gamma columns are collinear, so gamma is pinned to 0 with a
    warning instead of letting lstsq split alpha+gamma arbitrarily.
    """
    if len(samples) < 3:
        raise ValueError(f"need >= 3 samples to fit 3 terms, got {len(samples)}")
    if len({float(s.nbytes) for s in samples}) < 2:
        raise ValueError("need >= 2 distinct payload sizes to identify beta")
    fit_gamma = len({int(s.fanout) for s in samples}) >= 2
    if not fit_gamma:
        warnings.warn(
            "fit_fabric: all samples share one fanout, so the congestion "
            "term is not identifiable from alpha — fitting gamma_s = 0 "
            "(sweep >= 2 stage degrees to calibrate congestion)",
            UserWarning, stacklevel=2)
    rows, ys = [], []
    for s in samples:
        f = max(int(s.fanout), 1)
        gcol = [float(f - 1)] if fit_gamma else []
        if serial:
            rows.append([1.0] + gcol + [float(s.nbytes)])
            ys.append(s.time_s / f)
        else:
            rows.append([float(f)] + gcol + [float(s.nbytes)])
            ys.append(s.time_s)
    a = np.asarray(rows, np.float64)
    y = np.asarray(ys, np.float64)
    scale = np.maximum(np.abs(a).max(axis=0), 1e-30)
    x, *_ = np.linalg.lstsq(a / scale, y, rcond=None)
    x = x / scale
    alpha = max(float(x[0]), 1e-12)
    gamma = max(float(x[1]), 0.0) if fit_gamma else 0.0
    inv_beta = max(float(x[-1]), 1e-18)
    return Fabric(name=name, beta_bytes_per_s=1.0 / inv_beta, alpha_s=alpha,
                  floor_bytes=float(floor_bytes), gamma_s=gamma)


def fit_error(fabric: Fabric, samples: Sequence[StageSample], *,
              serial: bool = True) -> float:
    """Mean relative |modeled - measured| / measured over ``samples``
    (the bench's modeled-vs-measured error column)."""
    errs = [abs(fabric.stage_time(s.nbytes, s.fanout, serial=serial)
                - s.time_s) / max(s.time_s, 1e-12) for s in samples]
    return float(np.mean(errs)) if errs else 0.0


def measure_stage_samples(mesh=None, *, payload_entries=(256, 4096, 32768),
                          degrees: Optional[Sequence[int]] = None,
                          repeats: int = 3, seed: int = 0
                          ) -> List[StageSample]:
    """Time single butterfly stages (grouped ``all_to_all`` in shard_map)
    on the actual mesh — the calibration microbenchmark.

    For each *stage degree* ``k`` in ``degrees`` (default: the divisors of
    the mesh size among {2, 4, 8, 16, 32, m}; every k must divide the mesh
    size so the groups tile it) and each payload size, one jitted
    shard_map program exchanges the two streams a real butterfly stage
    ships — ``[k, c]`` uint32 indices *and* ``[k, c]`` float32 values
    (``STAGE_IDX_DTYPE`` / ``STAGE_VAL_DTYPE``) — within
    ``axis_index_groups`` of size k; best-of-``repeats`` wall time becomes
    a :class:`StageSample` with ``fanout = k - 1`` peers and ``nbytes =
    c * (idx.itemsize + val.itemsize)`` per destination.  (Pricing values
    alone would under-count the wire ~2x and skew every fabric fit.)
    Off-TPU (host devices) this calibrates the XLA-CPU collective cost —
    noisy but *measured*, which is the point; perf claims belong on real
    fabrics.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.launch.mesh import make_mesh
    if mesh is None:
        mesh = make_mesh((len(jax.devices()),), ("nodes",))
    axis = mesh.axis_names[0]
    m = int(mesh.shape[axis])
    if degrees is None:
        degrees = tuple(dict.fromkeys(
            k for k in (2, 4, 8, 16, 32, m) if 2 <= k <= m and m % k == 0))
    bad = [k for k in degrees if k < 2 or m % k]
    if bad:
        raise ValueError(
            f"stage degrees {bad} do not divide the mesh size {m}")
    rng = np.random.RandomState(seed)
    samples: List[StageSample] = []
    for k in degrees:
        groups = [list(range(g * k, (g + 1) * k)) for g in range(m // k)]

        def body(ib, vb):
            yi = lax.all_to_all(ib.reshape(ib.shape[1:]), axis,
                                split_axis=0, concat_axis=0,
                                axis_index_groups=groups)
            yv = lax.all_to_all(vb.reshape(vb.shape[1:]), axis,
                                split_axis=0, concat_axis=0,
                                axis_index_groups=groups)
            return (yi.reshape((1,) + yi.shape),
                    yv.reshape((1,) + yv.shape))

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                               out_specs=(P(axis), P(axis)),
                               check_vma=False))
        for c in payload_entries:
            xi = jnp.asarray(rng.randint(
                0, 1 << 31, size=(m, k, int(c))).astype(STAGE_IDX_DTYPE))
            xv = jnp.asarray(rng.rand(m, k, int(c)).astype(STAGE_VAL_DTYPE))
            jax.block_until_ready(fn(xi, xv))     # compile outside timing
            best = float("inf")
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(xi, xv))
                best = min(best, time.perf_counter() - t0)
            # Wire bytes per destination: both streams, actual itemsizes.
            nbytes = float(c) * float(STAGE_IDX_DTYPE.itemsize
                                      + STAGE_VAL_DTYPE.itemsize)
            samples.append(StageSample(nbytes=nbytes,
                                       fanout=k - 1, time_s=best))
    return samples


def calibrate_fabric(mesh=None, *, name: Optional[str] = None,
                     serial: bool = True, store: bool = False,
                     cache: Optional["PlanCache"] = None,
                     **measure_kw) -> Fabric:
    """Measure (:func:`measure_stage_samples`) + fit (:func:`fit_fabric`)
    in one call; ``store=True`` persists the fitted fabric in the plan
    cache for :func:`calibrated_fabric` lookups (keyed by backend and
    device count)."""
    import jax
    samples = measure_stage_samples(mesh, **measure_kw)
    ndev = len(jax.devices()) if mesh is None else math.prod(
        int(s) for s in mesh.devices.shape)
    name = name or f"calibrated-{jax.default_backend()}-{ndev}"
    fabric = fit_fabric(samples, serial=serial, name=name)
    if store:
        store_calibrated_fabric(fabric, backend=jax.default_backend(),
                                num_devices=ndev, cache=cache,
                                residual=fit_error(fabric, samples,
                                                   serial=serial))
    return fabric


def measure_plan(plan: ButterflyPlan, *, entries_per_node: int = 2048,
                 width: int = 1, mesh=None, merge: str = "sort",
                 repeats: int = 3, seed: int = 0) -> float:
    """Wall seconds for one full ``union_reduce`` under ``plan`` on the
    actual mesh — the timed-trial confirmation hook for
    :func:`select_plan` (``confirm=``) and the modeled-vs-measured rows of
    ``bench_autotune``."""
    import jax
    import jax.numpy as jnp

    from .api import SparseAllreduce
    m = plan.num_nodes
    ar = SparseAllreduce(m, plan.degrees, backend="device", mesh=mesh,
                         merge=merge)
    rng = np.random.RandomState(seed)
    idx = np.sort(rng.choice(1 << 20, size=(m, entries_per_node),
                             replace=True).astype(np.uint32), axis=1)
    shape = (m, entries_per_node) + ((width,) if width > 1 else ())
    val = rng.rand(*shape).astype(np.float32)
    cap = min(m * entries_per_node, 1 << 16)
    args = (jnp.asarray(idx), jnp.asarray(val))
    jax.block_until_ready(ar.union_reduce(*args, out_capacity=cap)[1])
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(ar.union_reduce(*args, out_capacity=cap)[1])
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# 2. Selection: rerank factorizations under the calibrated model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuneReport:
    """Outcome of one :func:`select_plan` sweep.

    ``plan`` is the winner; ``modeled_s`` its modeled reduce seconds;
    ``decreasing`` whether the paper's §IV degree-decreasing-with-depth
    structure holds for it; ``fallback`` records degenerate sweeps
    (``"prime"`` = only the flat plan exists, ``"depth-extended"`` =
    ``max_depth`` was lifted to Omega(M)); ``candidates`` the top-k
    ``(modeled_s, degrees)`` ranking; ``measured_s`` the timed-trial
    seconds per candidate when confirmation ran (else None).

    Overlap-aware sweeps (``overlap_compute_s``) additionally report the
    achieved-vs-rate-optimal position: ``rate_optimal_s`` is the
    schedule-independent allreduce lower bound for the swept payload
    (``repro.core.netmodel.rate_optimal_allreduce_s``, per *On the
    Computation Rate of All-Reduce*) and ``rate_fraction`` is
    ``rate_optimal_s / modeled_s`` — 1.0 means the winner meets the bound,
    smaller means headroom a better schedule could still claim.  Both are
    populated on every sweep (overlapped or not) so the overlap benches
    can chart the gap; ``overlap_compute_s`` echoes the request (None =
    bulk-synchronous ranking).
    """
    plan: ButterflyPlan
    modeled_s: float
    decreasing: bool
    fallback: Optional[str]
    candidates: Tuple[Tuple[float, Tuple[int, ...]], ...]
    measured_s: Optional[Dict[str, float]] = None
    rate_optimal_s: Optional[float] = None
    rate_fraction: Optional[float] = None
    overlap_compute_s: Optional[float] = None


def select_plan(num_nodes: int, n0: float, total_range: float,
                fabric: Fabric = EC2_2013, *,
                bytes_per_entry: float = 12.0, serial_nic: bool = True,
                top_k: int = 5, max_depth: int = 6,
                wire: str = "raw", value_width: int = 1,
                confirm: Optional[Callable[[ButterflyPlan], float]] = None,
                overlap_compute_s: Optional[float] = None
                ) -> TuneReport:
    """Rank all degree sequences under ``fabric`` with the power-law
    ``expected_counts`` compression curve; return a :class:`TuneReport`.

    ``confirm`` (e.g. ``functools.partial(measure_plan, mesh=mesh)``)
    re-ranks the ``top_k`` model candidates by timed trial — the model
    proposes, the hardware disposes.  Degenerate sweeps (prime M,
    truncating ``max_depth``) follow ``topology.tune``'s documented
    fallback and are recorded in ``report.fallback``.  A winner violating
    the paper's decreasing-degree structure is reported (and warned) but
    not overridden.

    ``wire`` re-ranks under the *encoded* per-stage byte model
    (``topology.wire_entry_bytes``): compression shrinks the bandwidth
    term without touching latency/congestion, so the optimal degree
    factorization can genuinely shift — that re-ranking is the point of
    tuning per wire format (see ``benchmarks/bench_wire.py``).

    ``overlap_compute_s`` re-ranks under the *overlapped* stage model
    (``topology.ButterflyPlan.modeled_overlap_time``): candidates are
    scored as serial overheads + max(bandwidth, overlap_compute_s), i.e.
    each stage's wire time hides behind that much independent compute (the
    bucketed gradient sync / rotated engine scan of ARCHITECTURE.md
    "Overlap & scheduling").  Once bandwidth hides, the residual
    serial-NIC cost is per-message setup — ``sum(k_l - 1)`` messages per
    node — so the optimum shifts toward deeper, lower-degree
    factorizations (binary in the limit), the opposite of the
    bandwidth-bound direction (benchmarks/bench_overlap.py).
    Every report also carries ``rate_optimal_s`` / ``rate_fraction`` — the
    achieved-vs-rate-optimal gap ROADMAP item 2 asks the benches to chart.
    """
    check_wire(wire)
    hidden = float(overlap_compute_s or 0.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        scored = tune(num_nodes, n0, total_range, fabric, bytes_per_entry,
                      serial_nic=serial_nic, top=max(int(top_k), 1),
                      max_depth=max_depth, wire=wire,
                      value_width=value_width, hidden_compute_s=hidden)
    fallback = None
    for w in caught:
        msg = str(w.message)
        if "prime" in msg:
            fallback = "prime"
        elif "truncate" in msg and fallback is None:
            fallback = "depth-extended"
        warnings.warn_explicit(w.message, w.category, w.filename, w.lineno)
    candidates = tuple((float(t), p.degrees) for t, p in scored)
    best_t, best = scored[0]
    measured = None
    if confirm is not None and len(scored) > 1:
        measured = {str(p): float(confirm(p)) for _, p in scored}
        best_t, best = min(scored, key=lambda tp: measured[str(tp[1])])
    decreasing = all(a >= b for a, b in zip(best.degrees, best.degrees[1:]))
    if not decreasing:
        warnings.warn(
            f"select_plan: winner {best} violates the paper's "
            f"decreasing-degree structure (SIV) — trust it only if it "
            f"came from a timed trial", UserWarning, stacklevel=2)
    from .netmodel import rate_optimal_allreduce_s
    payload = float(n0) * float(bytes_per_entry)
    opt_s = rate_optimal_allreduce_s(payload, num_nodes, fabric)
    return TuneReport(plan=best, modeled_s=float(best_t),
                      decreasing=decreasing, fallback=fallback,
                      candidates=candidates, measured_s=measured,
                      rate_optimal_s=opt_s,
                      rate_fraction=(opt_s / float(best_t)
                                     if best_t > 0 else 0.0),
                      overlap_compute_s=overlap_compute_s)


# ---------------------------------------------------------------------------
# 3. Persistent plan cache (checkpoint/store.py artifacts)
# ---------------------------------------------------------------------------

def cache_root() -> str:
    """Cache directory: ``$REPRO_PLAN_CACHE`` or ``~/.cache/repro/plans``."""
    return os.environ.get(CACHE_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "plans")


def _qlog(x: float) -> float:
    """Quantize to half-log2 buckets — the nnz-profile key granularity
    (plans are reused across <~ 1.4x workload-size drift; see TUNING.md
    invalidation rules)."""
    return round(2.0 * math.log2(max(float(x), 1.0))) / 2.0


def _digest(obj) -> str:
    return hashlib.sha1(
        json.dumps(obj, sort_keys=True).encode()).hexdigest()[:16]


def plan_cache_key(*, mesh: Sequence[Tuple[str, int]], nnz: float,
                   index_range: float, merge: str, replication: int,
                   width: int, fabric: Fabric,
                   serial_nic: bool = True,
                   shrunk_from: Optional[int] = None,
                   wire: str = "raw",
                   overlap_compute_s: float = 0.0) -> dict:
    """The cache key: mesh shape, quantized nnz profile, merge mode,
    replication, value width, fabric fingerprint, NIC serialization mode,
    key-schema version.  Any field changing = a different plan file
    (invalidation is purely key-miss; nothing is ever reused across these
    boundaries).

    ``shrunk_from`` marks survivor plans produced by ``repro.resilience``
    replanning a fleet that started at that logical size — keyed
    separately from native plans of equal size (the nnz profile carried
    over from the original fleet differs), and only added to the key when
    set, so every pre-existing digest is unchanged.

    ``wire`` keys plans per wire format: degrees tuned under compressed
    payloads are *not* valid answers for raw ones (the byte model differs),
    so a raw-tuned entry must never be served for e.g. ``delta+bf16``.
    Like ``shrunk_from`` it enters the key only when non-default, keeping
    every pre-existing "raw" digest stable.

    ``overlap_compute_s`` keys plans swept under the overlapped stage
    model (``select_plan(overlap_compute_s=...)``): degrees reranked with
    bandwidth hidden behind compute are not valid bulk-synchronous
    answers.  Quantized to half-log2 buckets like the nnz profile and —
    same convention again — only added when nonzero, so every
    pre-existing digest is unchanged."""
    key = {
        "kind": "plan", "version": _KEY_VERSION,
        "mesh": [[str(a), int(s)] for a, s in mesh],
        "nnz_bucket": _qlog(nnz), "range_bucket": _qlog(index_range),
        "merge": str(merge), "replication": int(replication),
        "width": int(width),
        "fabric": fabric.as_meta(),
        "serial_nic": bool(serial_nic),
    }
    if shrunk_from is not None:
        key["shrunk_from"] = int(shrunk_from)
    if check_wire(wire) != "raw":
        key["wire"] = str(wire)
    if overlap_compute_s:
        # seconds are fractional: bucket on the equivalent byte scale
        key["overlap_bucket"] = _qlog(
            float(overlap_compute_s) * fabric.beta_bytes_per_s)
    return key


def fabric_cache_key(*, backend: str, num_devices: int) -> dict:
    """Key for persisted calibrations: one fitted fabric per (backend,
    device count) — recalibrate with ``calibrate_fabric(store=True)``."""
    return {"kind": "fabric", "version": _KEY_VERSION,
            "backend": str(backend), "num_devices": int(num_devices)}


class PlanCache:
    """Directory of ``checkpoint/store.py`` artifacts keyed by digest.

    Each entry is ``<kind>-<digest>.npz`` (arrays: routing tensors for
    plan entries) + ``.meta.json`` (degrees, staging metadata, fabric
    parameters, the full key for debugging).  IO errors degrade to cache
    misses (counted in ``stats["errors"]``) — a broken cache can slow you
    down but never stop a run.
    """

    def __init__(self, root: Optional[str] = None):
        self._root = root
        self.stats = {"hits": 0, "misses": 0, "stores": 0, "errors": 0}

    @property
    def root(self) -> str:
        """Resolved cache directory (env var re-read when not pinned)."""
        return self._root or cache_root()

    def path(self, key: dict) -> str:
        """Extension-less artifact path for ``key``."""
        return os.path.join(self.root, f"{key['kind']}-{_digest(key)}")

    def load(self, key: dict):
        """``(meta, arrays)`` for ``key`` or ``None`` (counted miss)."""
        p = self.path(key)
        if not os.path.exists(p + ".meta.json"):
            self.stats["misses"] += 1
            return None
        try:
            from repro.checkpoint.store import load_flat
            if os.path.exists(p + ".npz"):
                arrays, meta = load_flat(p)
            else:
                arrays = {}
                with open(p + ".meta.json") as f:
                    meta = json.load(f)
            self.stats["hits"] += 1
            return meta, arrays
        except Exception:
            self.stats["errors"] += 1
            return None

    def store(self, key: dict, meta: dict,
              arrays: Optional[Dict[str, np.ndarray]] = None) -> None:
        """Persist ``meta`` (+ optional ``arrays``) under ``key``."""
        try:
            from repro.checkpoint.store import save
            save(self.path(key), arrays if arrays else
                 {"empty": np.zeros(0, np.int32)},
                 meta={**meta, "key": key})
            self.stats["stores"] += 1
        except OSError:
            self.stats["errors"] += 1

    def invalidate(self, key: dict) -> None:
        """Drop ``key``'s artifact (the ``--retune`` escape hatch)."""
        p = self.path(key)
        for ext in (".npz", ".meta.json"):
            try:
                os.remove(p + ext)
            except OSError:
                pass


_DEFAULT_CACHE: Optional[PlanCache] = None


def default_cache() -> PlanCache:
    """Process-wide :class:`PlanCache` rooted at :func:`cache_root`."""
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None:
        _DEFAULT_CACHE = PlanCache()
    return _DEFAULT_CACHE


def fabric_from_meta(meta: dict) -> Fabric:
    """Inverse of ``Fabric.as_meta`` (calibration / plan-cache reads)."""
    return Fabric(name=str(meta["name"]),
                  beta_bytes_per_s=float(meta["beta_bytes_per_s"]),
                  alpha_s=float(meta["alpha_s"]),
                  floor_bytes=float(meta.get("floor_bytes", 0.0)),
                  gamma_s=float(meta.get("gamma_s", 0.0)))


def store_calibrated_fabric(fabric: Fabric, *, backend: str,
                            num_devices: int,
                            cache: Optional[PlanCache] = None,
                            residual: Optional[float] = None) -> None:
    """Persist a fitted fabric for :func:`calibrated_fabric` lookups."""
    cache = cache or default_cache()
    meta = {"fabric": fabric.as_meta()}
    if residual is not None:
        meta["fit_residual"] = float(residual)
    cache.store(fabric_cache_key(backend=backend,
                                 num_devices=num_devices), meta)


def calibrated_fabric(*, backend: str, num_devices: int,
                      cache: Optional[PlanCache] = None,
                      default: Optional[Fabric] = None) -> Optional[Fabric]:
    """The persisted calibration for (backend, device count), or
    ``default`` when none exists."""
    cache = cache or default_cache()
    hit = cache.load(fabric_cache_key(backend=backend,
                                      num_devices=num_devices))
    if hit is None:
        return default
    meta, _ = hit
    return fabric_from_meta(meta["fabric"])


# ---------------------------------------------------------------------------
# resolve_degrees: what degrees="auto" goes through
# ---------------------------------------------------------------------------

def resolve_degrees(num_nodes: int, *, n0: float, total_range: float,
                    fabric: Fabric = EC2_2013, merge: str = "sort",
                    replication: int = 1, width: int = 1,
                    serial_nic: bool = True,
                    mesh_sig: Optional[Sequence[Tuple[str, int]]] = None,
                    cache: Optional[PlanCache] = None,
                    retune: bool = False, top_k: int = 5,
                    confirm: Optional[Callable] = None,
                    shrunk_from: Optional[int] = None,
                    wire: str = "raw"
                    ) -> Tuple[Tuple[int, ...], str]:
    """Cached, calibrated degree selection — returns ``(degrees, source)``
    with ``source`` in ``{"cache", "tuned"}``.

    Consults the persistent :class:`PlanCache` first (unless ``retune``),
    else runs :func:`select_plan` under ``fabric`` and stores the result
    (degrees + tune report + fabric parameters) for the next process.
    ``mesh_sig`` defaults to ``(("nodes", num_nodes),)``; pass the real
    ``(axis, size)`` layout so per-axis plans key separately.
    ``shrunk_from`` keys survivor replans separately (see
    :func:`plan_cache_key`) — a repeat shrink to the same survivor count
    is then a cache hit, which is what keeps ``repro.resilience``
    recovery cheap.
    ``wire`` tunes under the encoded byte model and keys the cache entry
    per wire format (a raw-tuned plan is never served for a compressed
    wire, and vice versa).
    """
    cache = cache or default_cache()
    sig = tuple(mesh_sig) if mesh_sig else (("nodes", int(num_nodes)),)
    if math.prod(s for _, s in sig) != num_nodes:
        raise ValueError(f"mesh_sig {sig} does not cover {num_nodes} nodes")
    key = plan_cache_key(mesh=sig, nnz=n0, index_range=total_range,
                         merge=merge, replication=replication, width=width,
                         fabric=fabric, serial_nic=serial_nic,
                         shrunk_from=shrunk_from, wire=wire)
    if not retune:
        hit = cache.load(key)
        if hit is not None:
            meta, _ = hit
            degrees = tuple(int(d) for d in meta.get("degrees", ()))
            if math.prod(degrees) == num_nodes or (
                    num_nodes == 1 and degrees == ()):
                return degrees, "cache"
    report = select_plan(num_nodes, n0, total_range, fabric,
                         serial_nic=serial_nic, top_k=top_k,
                         wire=wire, value_width=width,
                         confirm=confirm)
    cache.store(key, {
        "degrees": [int(d) for d in report.plan.degrees],
        "num_nodes": int(num_nodes),
        "modeled_s": report.modeled_s,
        "decreasing": report.decreasing,
        "fallback": report.fallback,
        "candidates": [[t, list(d)] for t, d in report.candidates],
        "measured_s": report.measured_s,
        "n0": float(n0), "total_range": float(total_range),
        "serial_nic": bool(serial_nic), "wire": str(wire),
    })
    return report.plan.degrees, "tuned"


# ---------------------------------------------------------------------------
# Frozen-plan persistence + in-process memo (zero-retrace cache hits)
# ---------------------------------------------------------------------------

plan_memo_stats = {"hits": 0, "misses": 0, "disk_hits": 0}
_PLANNED_MEMO: Dict[str, tuple] = {}   # insertion-ordered: LRU via re-insert
# Frozen plans + compiled reduce fns are heavyweight; cap the memo so a
# long-running process whose index pattern evolves (re-config per epoch,
# many engines over different graphs) cannot grow without bound.
PLANNED_MEMO_MAX = 64


def planner_version() -> str:
    """Digest of every source module frozen routing depends on
    (``planned.py``, ``simulator.py``, ``topology.py``, ``sparse_vec.py``,
    ``replication.py``).  Part of every persisted planned artifact's key,
    so editing the planning/hashing/grouping code auto-invalidates frozen
    routing from older code instead of silently reusing it."""
    global _PLANNER_VERSION
    if _PLANNER_VERSION is None:
        h = hashlib.sha1()
        here = os.path.dirname(os.path.abspath(__file__))
        for fname in ("planned.py", "simulator.py", "topology.py",
                      "sparse_vec.py", "replication.py"):
            with open(os.path.join(here, fname), "rb") as f:
                h.update(f.read())
        _PLANNER_VERSION = h.hexdigest()[:12]
    return _PLANNER_VERSION


_PLANNER_VERSION: Optional[str] = None


def planned_cache_key(fingerprint: str) -> dict:
    """Disk-cache key for one frozen-config artifact (the fingerprint
    already embeds :func:`planner_version`)."""
    return {"kind": "planned", "version": _KEY_VERSION, "fp": fingerprint}


def clear_plan_memo() -> None:
    """Drop the in-process planned/reduce-fn memo (tests, mesh teardown)."""
    _PLANNED_MEMO.clear()
    plan_memo_stats.update(hits=0, misses=0, disk_hits=0)


def _mesh_fingerprint(mesh) -> tuple:
    return (tuple(mesh.axis_names),
            tuple(int(s) for s in mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def planned_fingerprint(mesh, degrees: Sequence[int], replication: int,
                        dead, width: int, perm,
                        out_indices: Sequence[np.ndarray],
                        in_indices: Sequence[np.ndarray],
                        fabric: Optional[Fabric] = None) -> str:
    """Digest identifying one frozen config: mesh devices + plan shape +
    planner-code version + the exact index pattern (+ the stats-model
    fabric, since the cached ``ReduceStats`` were modeled under it).
    Same fingerprint => the frozen routing (and its compiled reduce fn)
    is reusable with zero re-planning/retracing."""
    h = hashlib.sha1()
    h.update(repr((_mesh_fingerprint(mesh), tuple(int(d) for d in degrees),
                   int(replication), tuple(sorted(dead or ())), int(width),
                   int(perm.mult), int(perm.xor), planner_version(),
                   None if fabric is None else
                   sorted(fabric.as_meta().items()))).encode())
    for group in (out_indices, in_indices):
        h.update(b"|group|")
        for arr in group:
            a = np.ascontiguousarray(np.asarray(arr))
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def memo_lookup(fingerprint: str):
    """In-process planned-config memo read (None on miss); hits refresh
    LRU recency."""
    hit = _PLANNED_MEMO.pop(fingerprint, None)
    if hit is not None:
        _PLANNED_MEMO[fingerprint] = hit       # re-insert: most recent
    plan_memo_stats["hits" if hit is not None else "misses"] += 1
    return hit


def memo_store(fingerprint: str, value: tuple) -> None:
    """In-process planned-config memo write (LRU-evicts past
    ``PLANNED_MEMO_MAX`` entries)."""
    _PLANNED_MEMO[fingerprint] = value
    while len(_PLANNED_MEMO) > PLANNED_MEMO_MAX:
        _PLANNED_MEMO.pop(next(iter(_PLANNED_MEMO)))


def stats_to_meta(stats) -> dict:
    """``ReduceStats`` -> JSON-able dict (plan-cache persistence)."""
    return {"config_time_s": stats.config_time_s,
            "reduce_time_s": stats.reduce_time_s,
            "overflow": int(stats.overflow),
            "stages": [dataclasses.asdict(s) for s in stats.stages]}


def stats_from_meta(meta: dict):
    """Inverse of :func:`stats_to_meta`."""
    from .simulator import ReduceStats, StageStats
    return ReduceStats(
        config_time_s=float(meta.get("config_time_s", 0.0)),
        reduce_time_s=float(meta.get("reduce_time_s", 0.0)),
        overflow=int(meta.get("overflow", 0)),
        stages=[StageStats(**s) for s in meta.get("stages", [])])


def planned_to_artifact(planned) -> Tuple[Dict[str, np.ndarray], dict]:
    """Serialize a ``PlannedSparseAllreduce`` into ``(arrays, meta)`` for
    :class:`PlanCache` — every frozen routing tensor plus the scalars and
    ``make_device_plan`` arguments needed to rebuild it byte-identically
    in a fresh process (:func:`planned_from_artifact`)."""
    arrays = {"user_scatter": planned.user_scatter,
              "bottom_gather": planned.bottom_gather,
              "bottom_hit": planned.bottom_hit,
              "user_gather": planned.user_gather}
    if planned.weights is not None:
        arrays["weights"] = np.asarray(planned.weights)
    layer_meta = []
    for i, L in enumerate(planned.layers):
        arrays[f"layer{i}/send_gather"] = L.send_gather
        arrays[f"layer{i}/merge_scatter"] = L.merge_scatter
        arrays[f"layer{i}/up_send_gather"] = L.up_send_gather
        arrays[f"layer{i}/up_recv_scatter"] = L.up_recv_scatter
        layer_meta.append({"merged_size": int(L.merged_size),
                           "up_size": int(L.up_size)})
    dp = planned.dplan
    meta = {
        "sorted_size": int(planned.sorted_size),
        "in_user_len": int(planned.in_user_len),
        "width": int(planned.width),
        "perm": {"mult": int(planned.perm.mult),
                 "xor": int(planned.perm.xor)},
        "layers": layer_meta,
        "dplan": {
            "axes": [[a, int(s)] for a, s in dp.axes],
            # logical degrees per axis, exactly the make_device_plan input
            "in_capacity": int(dp.in_capacity),
            "out_capacity": int(dp.out_capacity),
            "replication": int(dp.replication),
        },
    }
    return arrays, meta


def planned_from_artifact(arrays: Dict[str, np.ndarray], meta: dict,
                          degrees_per_axis: Dict[str, Tuple[int, ...]]):
    """Rebuild a ``PlannedSparseAllreduce`` from a cache artifact.

    ``degrees_per_axis`` must be the same *logical* per-axis degree dict
    the original ``make_device_plan`` call used (the caller knows it — it
    is part of the plan key / its meta)."""
    from .allreduce import make_device_plan
    from .planned import PlannedSparseAllreduce, _LayerMaps
    from .sparse_vec import HashPerm
    dmeta = meta["dplan"]
    dplan = make_device_plan(
        [(a, int(s)) for a, s in dmeta["axes"]],
        {a: tuple(int(x) for x in d) for a, d in degrees_per_axis.items()},
        in_capacity=int(dmeta["in_capacity"]),
        out_capacity=int(dmeta["out_capacity"]),
        replication=int(dmeta["replication"]))
    layers = []
    for i, lm in enumerate(meta["layers"]):
        layers.append(_LayerMaps(
            send_gather=arrays[f"layer{i}/send_gather"],
            merge_scatter=arrays[f"layer{i}/merge_scatter"],
            merged_size=int(lm["merged_size"]),
            up_send_gather=arrays[f"layer{i}/up_send_gather"],
            up_recv_scatter=arrays[f"layer{i}/up_recv_scatter"],
            up_size=int(lm["up_size"])))
    weights = arrays.get("weights")
    return PlannedSparseAllreduce(
        dplan=dplan,
        perm=HashPerm(mult=int(meta["perm"]["mult"]),
                      xor=int(meta["perm"]["xor"])),
        width=int(meta["width"]),
        user_scatter=arrays["user_scatter"],
        sorted_size=int(meta["sorted_size"]),
        layers=layers,
        bottom_gather=arrays["bottom_gather"],
        bottom_hit=arrays["bottom_hit"],
        user_gather=arrays["user_gather"],
        in_user_len=int(meta["in_user_len"]),
        weights=weights)
