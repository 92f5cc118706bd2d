"""Device-resident iterative graph engine (paper §I-A.2, §III-B, Fig 8-9).

The paper's headline workloads are *iterative*: PageRank, HADI and spectral
partitioning amortize one ``config`` over many ``reduce`` rounds.  The
per-call device path (``SparseAllreduce.reduce``) still pays one host
staging + one jitted dispatch per round; this module closes that gap by
composing the **local SpMV** (an ELL gather + row sum,
:func:`ell_matvec`) with the **planned sparse-allreduce reduce**
(``PlannedSparseAllreduce.reduce_on_device``) inside one jitted
multi-iteration step:

    engine = GraphEngine(out_sets, in_sets, app, degrees=(4, 2), mesh=mesh)
    final_state, last_out, traj = engine.run(k, state0, extras)

``run(k)`` executes k rounds — ``lax.scan`` over a shard_map step whose
body is ``out = app.out_fn(state)`` → ``in = reduce_on_device(out)`` →
``state = app.update_fn(state, in)`` — with a **single host↔device
round-trip and a single jitted dispatch**, reusing the frozen config /
staging layout (``SparseAllreduce.planned_parts`` /
``staging_metadata``) across all rounds.  The routing tensors are
scan-invariant, so XLA hoists them; per-round work is the SpMV, the
2·depth ``all_to_all`` phases of the butterfly, and the app update.

Backend contract: the engine is the ``backend="device"`` path of the graph
apps (``pagerank`` / ``hadi`` / ``power_iteration`` route here); their
numpy-per-round ``backend="sim"`` loops are preserved untouched as the
oracle.  Replication is not plumbed through the engine yet — construct it
unreplicated (the planned path underneath does support r-way replication
for per-call reduces).

Scaling caveat: the stacked ELL tables pad every partition to the global
max rows × max per-row nonzeros.  The hash permutation balances *columns*
(that is the paper's point), not row degrees — power-law hub rows inflate
``K``; a segmented-CSR kernel is the planned fix for hub-heavy partitions.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import numpy as np

from repro.core import SparseAllreduce
from repro.core.netmodel import EC2_2013, Fabric


# ---------------------------------------------------------------------------
# Vectorized ELL construction (shared with Partition.ell_tables)
# ---------------------------------------------------------------------------

def build_ell(rows: np.ndarray, cols: np.ndarray, weights: np.ndarray,
              n_rows: int, min_k: int = 1
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized ELL build: COO triplets -> padded ``[n_rows, K]`` tables.

    ``rows`` / ``cols`` / ``weights``: [E] coordinate triplets (local row
    and column positions).  Returns ``(ell_cols int32, ell_wts float32)``
    with ``K = max(row_count, min_k)``; empty slots are ``-1`` / ``0``.
    Entries within a row keep their original (stable) edge order — the
    same layout the old per-edge Python loop produced, without the loop:
    a stable argsort groups rows, and each entry's slot is its offset from
    its row's start (``arange(E) - row_start[row]``).
    """
    if n_rows == 0:
        return (np.full((0, min_k), -1, np.int32),
                np.zeros((0, min_k), np.float32))
    order = np.argsort(rows, kind="stable")
    r = rows[order]
    counts = np.bincount(r, minlength=n_rows)
    kmax = max(int(counts.max(initial=0)), min_k)
    starts = np.zeros(n_rows + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.arange(len(r), dtype=np.int64) - starts[r]
    ell_cols = np.full((n_rows, kmax), -1, np.int32)
    ell_wts = np.zeros((n_rows, kmax), np.float32)
    ell_cols[r, slots] = np.asarray(cols)[order]
    ell_wts[r, slots] = np.asarray(weights)[order]
    return ell_cols, ell_wts


def stack_ell(tables, n_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    """Stack per-node ``build_ell`` outputs into ``[M, n_rows, K]`` tensors
    (K = global max; rows/K padded with ``-1`` / ``0``) — the static
    per-device extras the engine shards over the mesh."""
    m = len(tables)
    kmax = max(max(c.shape[1] for c, _ in tables), 1)
    cols = np.full((m, n_rows, kmax), -1, np.int32)
    wts = np.zeros((m, n_rows, kmax), np.float32)
    for i, (c, w) in enumerate(tables):
        cols[i, : c.shape[0], : c.shape[1]] = c
        wts[i, : w.shape[0], : w.shape[1]] = w
    return cols, wts


def ell_matvec(cols, wts, x):
    """``y[r] = sum_k wts[r,k] * x[cols[r,k]]`` with ``cols < 0`` padding.

    ``x``: [N] or [N, W] (per-device state).  An XLA gather and a row sum;
    the partition's ``x`` is far larger than a kernel's fast memory, so
    there is no Pallas form of this product.
    """
    import jax.numpy as jnp
    safe = jnp.maximum(cols, 0)
    g = x[safe]                                  # [R, K] or [R, K, W]
    mask = (cols >= 0).astype(x.dtype)
    if x.ndim == 1:
        return jnp.sum(wts * mask * g, axis=1)
    return jnp.sum((wts * mask)[..., None] * g, axis=1)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineApp:
    """Per-round behaviour of one iterative workload, staged into the jit.

    ``out_fn(state, extras) -> out``: per-device traced fn producing the
    round's outbound values ``[u_cap(,W)]`` from the per-device state
    pytree (typically the local SpMV over ELL extras).

    ``update_fn(state, in_raw, extras, axis_name) -> state``: per-device
    traced fn folding the reduced values ``[uin_cap(,W)]`` back into the
    state.  ``axis_name`` is the mesh axis — apps may run extra collectives
    (e.g. spectral's norm ``psum``) inside the same dispatch.

    ``value_width``: trailing value width W (1 for scalar-per-index).
    """
    out_fn: Callable[[Any, Any], Any]
    update_fn: Callable[[Any, Any, Any, str], Any]
    value_width: int = 1
    name: str = "app"


class GraphEngine:
    """k iterations on device per dispatch (see module docstring).

    Construction runs the paper's ``config`` once (host numpy) and freezes
    the plan; ``run`` then executes whole k-round blocks.  Device backend
    only — requires a mesh (or the process default devices) with exactly
    ``len(out_sets)`` devices.

    ``report`` (also :meth:`sync_report`) tracks the amortization
    contract: ``dispatches`` counts jitted invocations, ``rounds`` total
    iterations executed, ``step_traces`` how many times the per-round body
    was traced — after any ``run(k)``, dispatches/traces grow by exactly
    one however large k is (asserted in tests/test_graph_engine.py).

    ``degrees="auto"`` resolves through the calibrated autotuner's
    persistent plan cache (``repro.core.autotune``, TUNING.md), and the
    ``config`` underneath is memo/disk-cached: a second engine over the
    same mesh + index pattern reuses the frozen plan without host
    re-planning (``report["config_cache"]`` says which tier hit).
    ``plan_cache`` / ``retune`` forward to ``SparseAllreduce`` — pass
    ``retune=True`` after recalibrating the fabric, ``plan_cache=False``
    to opt out of the disk tier.

    ``overlap=True`` selects the double-buffered round schedule
    (:meth:`_build_overlap`; ARCHITECTURE.md "Overlap & scheduling"):
    round k's top-half return shares a scanned body with round k+1's SpMV
    and down half, with the in-flight bottom buffer carried across the
    scan boundary.  Same ops, same collective totals, bitwise-identical
    results — only the issue order changes (k=1 has nothing to rotate and
    runs the synchronous body).  The run-fn cache, zero-retrace contract
    and ``report`` semantics are unchanged.
    """

    def __init__(self, out_sets, in_sets, app: EngineApp, *,
                 degrees="auto", mesh=None, seed: int = 0,
                 fabric: Fabric = EC2_2013, plan_cache=True,
                 retune: bool = False, overlap: bool = False):
        self.app = app
        self.overlap = bool(overlap)
        self.num_nodes = len(out_sets)
        self.out_sets = [np.asarray(o, np.uint32) for o in out_sets]
        self.in_sets = [np.asarray(i, np.uint32) for i in in_sets]
        self.seed = seed
        self.fabric = fabric
        self.plan_cache_arg = plan_cache
        self.ar = SparseAllreduce(self.num_nodes, degrees, backend="device",
                                  mesh=mesh, seed=seed, fabric=fabric,
                                  value_width=app.value_width,
                                  plan_cache=plan_cache, retune=retune)
        self.config_stats = self.ar.config(self.out_sets, self.in_sets)
        self.config_cache = self.ar.config_cache
        self.planned, self.mesh = self.ar.planned_parts()
        meta = self.ar.staging_metadata()
        self.u_cap: int = meta["u_cap"]
        self.uin_cap: int = meta["uin_cap"]
        self.out_lens = meta["out_lens"]
        self.in_lens = meta["in_lens"]
        self.axis: str = self.mesh.axis_names[0]
        self._routing = tuple(self.planned.device_args())
        self._run_cache: Dict[Tuple[int, str], Callable] = {}
        self.report = {"dispatches": 0, "rounds": 0, "step_traces": 0}

    # ---------------------------------------------------------------------
    def remesh(self, mesh) -> "GraphEngine":
        """The same engine program on a different device set.

        The recovery move for whole-device loss when spare devices exist
        (``repro.resilience.engine``): the partition, index pattern,
        *resolved* degrees, and seed carry over unchanged, so the rebuilt
        plan's routing — and therefore every reduce result — is
        bit-identical to this engine's; only the mesh binding differs.
        Plan configs are memo-keyed on the mesh's device ids
        (``repro.core.autotune``), so remapping back to a previously used
        device set is a zero-retrace memo hit.  ``mesh`` must span
        ``num_nodes`` devices.
        """
        return GraphEngine(self.out_sets, self.in_sets, self.app,
                           degrees=self.ar.plan.degrees, mesh=mesh,
                           seed=self.seed, fabric=self.fabric,
                           plan_cache=self.plan_cache_arg, retune=False,
                           overlap=self.overlap)

    # -- static per-reduce sync structure ---------------------------------
    def sync_report(self) -> dict:
        """Per-round sync accounting: one reduce = ``depth`` down +
        ``depth`` up ``all_to_all`` phases; host round-trips equal
        dispatches (one per ``run`` call), not rounds.  ``overlap``
        reports the schedule: the rotated double-buffered scan keeps the
        same per-round collective total, split as ``depth`` prologue +
        ``depth`` epilogue phases outside the scan plus ``2 * depth`` per
        interior round inside it (audited by
        ``repro.analysis.auditor.audit_engine``)."""
        return dict(self.report,
                    butterfly_depth=self.planned.depth,
                    reduce_collectives_per_round=2 * self.planned.depth,
                    host_roundtrips=self.report["dispatches"],
                    config_cache=self.config_cache,
                    overlap=self.overlap)

    # ---------------------------------------------------------------------
    def _build_overlap(self, k: int, collect: str) -> Callable:
        """Double-buffered k-round pipeline (``overlap=True``, k >= 2).

        The synchronous body runs SpMV → down half → up half → update, so
        both butterfly halves sit back-to-back with no independent work
        adjacent to either.  This build *rotates* the loop at the round
        boundary: the carry holds round j's in-flight bottom-half buffer
        (``[q_cap(,W)]`` root partials, issued at the end of body j-1 and
        consumed at the start of body j), so each scanned body is

            up half of round j  →  update  →  SpMV of round j+1
                                →  down half of round j+1

        — round j's top-half return and round j+1's SpMV/down issue share
        one body, with the scan boundary between a buffer's issue and its
        consumption (the async-friendly shape XLA's collective pipeliner
        and latency-hiding scheduler need).  Round 1's SpMV + down half
        run as a prologue before the scan and round k's up half + update
        as an epilogue after it, so the per-dispatch collective total is
        unchanged: ``depth`` + (k-1) * ``2 depth`` + ``depth`` = k *
        ``2 depth``.  Every round still executes the identical op
        sequence on identical inputs — results are bitwise equal to the
        synchronous build (tests/test_overlap.py) — and the frozen
        routing / run-fn caches are shared, so the zero-retrace contract
        holds unchanged (tests/test_graph_engine.py).
        """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax.tree_util import tree_map

        from repro.compat import shard_map

        planned, app, axis = self.planned, self.app, self.axis
        spec = P(axis)

        def unsq(t):
            return tree_map(lambda a: a.reshape(a.shape[1:]), t)

        def resq(t):
            return tree_map(lambda a: a.reshape((1,) + a.shape), t)

        def pre_body(state, extras, *routing):
            # round 1: SpMV + bottom half, issued before the scan starts
            s, e = unsq(state), unsq(extras)
            out = app.out_fn(s, e)
            bottom = planned.reduce_down_on_device(out, *routing)
            return resq(bottom), resq(out)

        def mid_body(state, bottom, extras, *routing):
            # round j's top-half return + round j+1's SpMV and down half
            self.report["step_traces"] += 1
            s, b, e = unsq(state), unsq(bottom), unsq(extras)
            in_raw = planned.reduce_up_on_device(b, *routing)
            s2 = app.update_fn(s, in_raw, e, axis)
            out = app.out_fn(s2, e)
            b2 = planned.reduce_down_on_device(out, *routing)
            return resq(s2), resq(b2), resq(out)

        def post_body(state, bottom, extras, *routing):
            # round k: top-half return + update, after the scan drains
            s, b, e = unsq(state), unsq(bottom), unsq(extras)
            in_raw = planned.reduce_up_on_device(b, *routing)
            return resq(app.update_fn(s, in_raw, e, axis))

        rspecs = (spec,) * len(self._routing)
        smap_pre = shard_map(pre_body, mesh=self.mesh,
                             in_specs=(spec, spec) + rspecs,
                             out_specs=(spec, spec), check_vma=False)
        smap_mid = shard_map(mid_body, mesh=self.mesh,
                             in_specs=(spec, spec, spec) + rspecs,
                             out_specs=(spec, spec, spec), check_vma=False)
        smap_post = shard_map(post_body, mesh=self.mesh,
                              in_specs=(spec, spec, spec) + rspecs,
                              out_specs=spec, check_vma=False)

        def run_k(state, extras, *routing):
            bottom, out1 = smap_pre(state, extras, *routing)

            def scan_body(carry, _):
                s, b, _last = carry
                s2, b2, out = smap_mid(s, b, extras, *routing)
                ys = s2 if collect == "trajectory" else None
                return (s2, b2, out), ys

            (s, b, last_out), traj = lax.scan(
                scan_body, (state, bottom, out1), None, length=k - 1)
            final = smap_post(s, b, extras, *routing)
            if collect == "trajectory":
                traj = tree_map(
                    lambda ys, f: jnp.concatenate([ys, f[None]], axis=0),
                    traj, final)
            return final, last_out, traj

        return jax.jit(run_k)

    # ---------------------------------------------------------------------
    def _build(self, k: int, collect: str) -> Callable:
        if self.overlap and k >= 2:
            return self._build_overlap(k, collect)
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P
        from jax.tree_util import tree_map

        from repro.compat import shard_map

        planned, app, axis = self.planned, self.app, self.axis
        spec = P(axis)
        w = app.value_width
        out_shape = (self.num_nodes, self.u_cap) + ((w,) if w > 1 else ())

        def step_body(state, extras, *routing):
            # per-device blocks arrive with a leading mesh dim of size 1
            self.report["step_traces"] += 1
            s = tree_map(lambda a: a.reshape(a.shape[1:]), state)
            e = tree_map(lambda a: a.reshape(a.shape[1:]), extras)
            out = app.out_fn(s, e)
            in_raw = planned.reduce_on_device(out, *routing)
            s2 = app.update_fn(s, in_raw, e, axis)
            return (tree_map(lambda a: a.reshape((1,) + a.shape), s2),
                    out.reshape((1,) + out.shape))

        smap = shard_map(
            step_body, mesh=self.mesh,
            in_specs=(spec, spec) + (spec,) * len(self._routing),
            out_specs=(spec, spec), check_vma=False)

        def run_k(state, extras, *routing):
            def scan_body(carry, _):
                s, _last = carry
                s2, out = smap(s, extras, *routing)
                ys = s2 if collect == "trajectory" else None
                return (s2, out), ys

            zero_out = jnp.zeros(out_shape, jnp.float32)
            (final, last_out), traj = lax.scan(
                scan_body, (state, zero_out), None, length=k)
            return final, last_out, traj

        return jax.jit(run_k)

    # ---------------------------------------------------------------------
    def run_fn(self, k: int, collect: str = "last"):
        """The jitted k-round callable ``run(state, extras, *routing) ->
        (final, last_out, traj)`` that :meth:`run` dispatches, without
        executing it.  ``engine.run_fn(k)(state, extras,
        *engine.routing_args())`` is exactly one dispatch; the static
        auditor (``repro.analysis.auditor``) traces this to verify the
        whole k-round block lowers to a single ``lax.scan`` with all
        collectives inside.  Cached per ``(k, collect)`` like :meth:`run`.
        """
        if collect not in ("last", "trajectory"):
            raise ValueError(f"collect must be 'last' or 'trajectory', "
                             f"got {collect!r}")
        if k < 1:
            raise ValueError(f"need k >= 1 rounds, got {k}")
        fn = self._run_cache.get((k, collect))
        if fn is None:
            fn = self._run_cache[(k, collect)] = self._build(k, collect)
        return fn

    def routing_args(self):
        """The frozen routing tensors :meth:`run` threads into every
        dispatch (positionally after ``state, extras``)."""
        return self._routing

    # ---------------------------------------------------------------------
    def run(self, k: int, state, extras=None, *, collect: str = "last"):
        """Execute k rounds in ONE jitted dispatch.

        ``state``: pytree of ``[M, ...]`` arrays (leading dim = logical
        nodes; typically ``[M, uin_cap(,W)]`` per-node vectors), sharded
        over the mesh.  ``extras``: pytree of iteration-invariant ``[M,
        ...]`` arrays handed to the app fns per-device (e.g. stacked ELL
        tables).  ``collect="trajectory"`` additionally stacks the
        post-update state of every round (``[k, M, ...]`` leaves — HADI's
        per-hop curve needs this); ``"last"`` keeps memory flat.

        Returns ``(final_state, last_out, traj)`` — ``last_out`` is round
        k's pre-reduce outbound values ``[M, u_cap(,W)]`` (PageRank's
        final partial products), ``traj`` is ``None`` unless collecting.
        Compiled functions are cached per ``(k, collect)``; repeated calls
        with the same k re-dispatch without re-tracing.
        """
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        from jax.tree_util import tree_map
        fn = self.run_fn(k, collect)
        # host arrays go straight to their shards: staged whole on one
        # device first, the stacked ELL tables of M partitions do not fit
        shard = NamedSharding(self.mesh, P(self.axis))
        state, extras = jax.device_put(
            (state, extras if extras is not None else {}), shard)
        final, last_out, traj = fn(state, extras, *self._routing)
        self.report["dispatches"] += 1
        self.report["rounds"] += k
        return final, last_out, traj
