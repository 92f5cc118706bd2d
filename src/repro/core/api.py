"""Public Sparse Allreduce API — the paper's two-call interface (§III-B).

    ar = SparseAllreduce(num_nodes=64, degrees=(16, 4))       # or degrees="auto"
    ar.config(out_indices, in_indices)     # once per index pattern
    new_vals = ar.reduce(out_values)       # every iteration

Backends:
  * ``backend="sim"``     — message-level numpy reference (+ timing model,
    replication, failures).  Default; runs anywhere.
  * ``backend="device"``  — host config + jitted shard_map reduce on a JAX
    mesh (the production TPU path; works on any device count incl. forced
    host devices).

Both backends take ``replication=r`` + ``dead`` (paper §V): ``num_nodes``
logical shards are hosted r-way redundantly — on the device backend over
``r * num_nodes`` physical mesh devices laid out per
``repro.core.replication.replica_groups`` — and the reduce completes with
unchanged results for any failure set that leaves each replica group at
least one alive member, raising ``DeadLogicalNode`` otherwise.  Failure
schedules for tests/benches live in ``repro.core.faults``; cost and
completion-probability curves in ``benchmarks/bench_fault_tolerance.py``.

``degrees="auto"`` resolves through the calibrated autotuner
(:mod:`repro.core.autotune`): cached plans are read from the persistent
plan cache (``$REPRO_PLAN_CACHE`` or ``~/.cache/repro/plans``) before the
cost-model sweep runs, and on the device backend :meth:`config` both
memoizes the frozen plan in-process (a repeat config with the same index
pattern reuses the compiled reduce with **zero retraces**) and persists
the frozen routing tensors so a restarted process skips host re-planning.
See TUNING.md for the workflow, keying and invalidation rules.

The gather-all (union) device primitive used by the training framework is
exposed separately in :mod:`repro.core.allreduce`.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Set, Tuple

import numpy as np

from .netmodel import EC2_2013, Fabric
from .sparse_vec import HashPerm
from .simulator import ReduceStats, SimSparseAllreduce
from .topology import ButterflyPlan, check_wire, tune


class SparseAllreduce:
    """The paper's two-call primitive (module docstring): ``config`` once
    per index pattern, ``reduce`` every iteration, over a sim or device
    backend with optional r-way replication and autotuned degrees."""

    def __init__(self, num_nodes: int, degrees="auto", *,
                 backend: str = "sim",
                 replication: int = 1, dead: Optional[Set[int]] = None,
                 fabric: Fabric = EC2_2013, seed: int = 0,
                 value_width: int = 1, mesh=None,
                 expected_nnz: float = 1e5, index_range: float = 1e6,
                 merge: str = "sort", wire: str = "raw",
                 plan_cache=True, retune: bool = False):
        """``merge`` ("sort" | "fused" | "banded") picks the
        per-butterfly-layer merge used by the dynamic-index union path
        (:meth:`union_reduce`): concatenate-and-resort, the fused Pallas
        rank-merge pipeline (``repro.kernels.ops.merge_sorted_runs``), or
        its band-limited variant that exploits stream sortedness to cut
        the per-layer tile work to near-linear.  The planned ``reduce``
        path freezes routing at ``config`` time and has no merge stage, so
        the knob does not affect it.

        ``wire`` ("raw" | "delta" | "delta+bf16" | "delta+int8ef") picks
        the on-wire payload encoding of the union path (see
        ``repro.kernels.wirecodec``): raw ships uint32 indices + f32
        values; ``delta`` bit-packs the sorted index stream at each
        stage's residual width (bit-identical results); the lossy modes
        additionally quantize values to bf16 / per-row-scaled int8.  The
        knob re-ranks ``degrees="auto"`` under the encoded byte model and
        keys the plan cache per wire format.  The planned ``reduce`` path
        ships pre-routed values only (no index stream), so raw/delta are
        equivalent no-ops there and the lossy modes are rejected; the sim
        backend models bytes, not value precision, and rejects lossy modes
        at construction.

        ``plan_cache`` controls the autotuner's persistent cache
        (``repro.core.autotune``): ``True`` (default) uses the process
        cache at ``$REPRO_PLAN_CACHE`` / ``~/.cache/repro/plans``, a
        ``PlanCache`` instance pins a specific root, ``False`` disables
        persistence (``degrees="auto"`` still tunes, ``config`` still
        memoizes in-process).  ``retune=True`` bypasses cached degree
        reads and overwrites them (the ``--retune`` escape hatch)."""
        from .allreduce import MERGE_MODES
        if merge not in MERGE_MODES:
            raise ValueError(
                f"merge must be one of {MERGE_MODES}, got {merge!r}")
        from .autotune import PlanCache, default_cache
        if plan_cache is True:
            self.plan_cache = default_cache()
        elif plan_cache is False or plan_cache is None:
            self.plan_cache = None
        elif isinstance(plan_cache, PlanCache):
            self.plan_cache = plan_cache
        else:
            raise ValueError(
                f"plan_cache must be True, False or a PlanCache (to pin a "
                f"root, pass PlanCache(root=...)), got {plan_cache!r}")
        self.merge = merge
        self.wire = check_wire(wire)
        if backend == "sim" and self.wire in ("delta+bf16", "delta+int8ef"):
            raise NotImplementedError(
                f"backend='sim' models message bytes, not value precision; "
                f"wire={self.wire!r} has no sim semantics (use 'raw' or "
                f"'delta', or backend='device')")
        self.num_nodes = num_nodes
        self.degrees_source = "explicit"
        if degrees == "auto":
            from .autotune import resolve_degrees
            if self.plan_cache is not None:
                degrees, self.degrees_source = resolve_degrees(
                    num_nodes, n0=expected_nnz, total_range=index_range,
                    fabric=fabric, merge=merge, replication=replication,
                    width=value_width, cache=self.plan_cache, retune=retune,
                    wire=self.wire)
            else:
                plan = tune(num_nodes, n0=expected_nnz,
                            total_range=index_range, fabric=fabric,
                            wire=self.wire, value_width=value_width)
                degrees, self.degrees_source = plan.degrees, "tuned"
        self.plan = ButterflyPlan(num_nodes, tuple(degrees))
        self.backend = backend
        self.perm = HashPerm.make(seed)
        self.width = value_width
        self.fabric = fabric
        self.replication = replication
        self.dead = dead
        self.mesh = mesh
        self._mesh_used = None       # mesh bound at config (device backend)
        self._sim: Optional[SimSparseAllreduce] = None
        self._planned = None
        self._reduce_fn = None
        self._u_cap = None
        self._in_lens = None
        self._union_cache = {}
        # union-path plan resolution counters (serving tier / benches):
        # a "hit" reuses a compiled union pipeline from _union_cache, a
        # "miss" plans + traces a new one.  Cumulative over the instance
        # lifetime (reconfig_dead clears the cache, so calls after it
        # miss again until re-trace).
        self.union_plan_stats = {"hits": 0, "misses": 0}
        self._staging = None
        self._stage_rows = self._stage_cols = None
        self._first_alive = None
        # how the last config()/reconfig_dead() was satisfied on the device
        # backend: None (no config yet / sim) | "fresh" | "memo" | "disk"
        # | "repair" (dead-set swap without host replanning)
        self.config_cache = None

    @property
    def num_physical(self) -> int:
        """Physical device count: ``num_nodes`` logical shards × r."""
        return self.num_nodes * self.replication

    # ------------------------------------------------------------------
    def config(self, out_indices: Sequence[np.ndarray],
               in_indices: Sequence[np.ndarray]) -> ReduceStats:
        """The paper's ``config`` call — run once per index pattern.

        ``out_indices`` / ``in_indices``: one uint32 array per *logical*
        node (sorted-unique not required for out; in defines the order of
        the per-node result rows).  Freezes all routing: on ``sim`` it
        builds the message-level schedule; on ``device`` it plans the
        static gather/scatter tensors and jit-compiles the reduce
        (``plan_sparse_allreduce`` + ``make_reduce_fn``), binding the mesh
        (``self.mesh`` or a fresh one over all devices).  Returns modeled
        ``ReduceStats`` from a simulator shadow config on both backends.
        Amortization contract: every subsequent :meth:`reduce` (any number
        of iterations) reuses this plan; re-calling ``config`` re-plans.

        Device configs are additionally cached (``repro.core.autotune``):
        an identical (mesh, degrees, replication, dead, width, index
        pattern) config in the same process reuses the frozen plan AND its
        compiled reduce fn — zero host re-planning, zero retraces
        (``self.config_cache == "memo"``); across a process restart the
        frozen routing tensors + modeled stats are reloaded from the
        persistent plan cache, skipping the host planning pass
        (``"disk"``).  Set ``plan_cache=False`` at construction to opt
        out of the disk tier.
        """
        self._in_lens = [len(i) for i in in_indices]
        self._out_lens = [len(o) for o in out_indices]
        self._staging = None                  # re-config invalidates staging
        if self.backend == "sim":
            self._sim = SimSparseAllreduce(
                self.plan, replication=self.replication, dead=self.dead,
                perm=self.perm, fabric=self.fabric, value_width=self.width)
            return self._sim.config(out_indices, in_indices)
        elif self.backend == "device":
            if self.wire in ("delta+bf16", "delta+int8ef"):
                raise NotImplementedError(
                    f"the planned reduce path ships pre-routed values only "
                    f"(no index stream), and quantized planned payloads are "
                    f"not implemented; wire={self.wire!r} is only supported "
                    f"on the union path (union_reduce / train sync)")
            from .replication import first_alive_replicas
            r, m_phys = self.replication, self.num_physical
            # Validates the failure set before touching the mesh: raises
            # DeadLogicalNode when a whole replica group is dead, exactly
            # like SimSparseAllreduce (and with r=1, on any failure).
            self._first_alive = first_alive_replicas(m_phys, r, self.dead)
            import jax

            from . import autotune
            from .allreduce import make_device_plan
            from .planned import plan_sparse_allreduce
            from repro.launch.mesh import make_mesh
            mesh = self.mesh
            if mesh is None:
                n = len(jax.devices())
                if n % m_phys:
                    raise ValueError(
                        f"{n} devices for {m_phys} physical nodes "
                        f"({self.num_nodes} logical x r={r})")
                mesh = make_mesh((m_phys,), ("nodes",))
            axis = mesh.axis_names[0]
            self._mesh_used = mesh
            fp = autotune.planned_fingerprint(
                mesh, self.plan.degrees, r, self.dead, self.width,
                self.perm, out_indices, in_indices, fabric=self.fabric)
            memo = autotune.memo_lookup(fp)
            if memo is not None:
                # zero-retrace hit: frozen plan AND compiled reduce reused
                self._planned, self._reduce_fn, stats = memo
                self._u_cap = self._planned.user_scatter.shape[1]
                self.config_cache = "memo"
                return stats
            planned = stats = None
            pkey = autotune.planned_cache_key(fp)
            if self.plan_cache is not None:
                hit = self.plan_cache.load(pkey)
                if hit is not None:
                    meta, arrays = hit
                    try:
                        planned = autotune.planned_from_artifact(
                            arrays, meta, {axis: self.plan.degrees})
                        stats = autotune.stats_from_meta(meta["stats"])
                        self.config_cache = "disk"
                    except Exception:
                        planned = stats = None   # corrupt entry -> replan
            if planned is None:
                dplan = make_device_plan(
                    [(axis, m_phys)], {axis: self.plan.degrees},
                    in_capacity=max(self._out_lens),
                    out_capacity=sum(self._out_lens), replication=r)
                planned = plan_sparse_allreduce(
                    dplan, out_indices, in_indices, perm=self.perm,
                    width=self.width, dead=self.dead)
                # stats come from a simulator shadow-config (same routing,
                # r-fold message accounting when replicated)
                shadow = SimSparseAllreduce(self.plan, replication=r,
                                            dead=self.dead, perm=self.perm,
                                            fabric=self.fabric,
                                            value_width=self.width)
                stats = shadow.config(out_indices, in_indices)
                self.config_cache = "fresh"
                if self.plan_cache is not None:
                    arrays, meta = autotune.planned_to_artifact(planned)
                    meta["stats"] = autotune.stats_to_meta(stats)
                    meta["staging"] = {
                        "u_cap": planned.u_cap, "uin_cap": planned.uin_cap,
                        "out_lens": list(self._out_lens),
                        "in_lens": list(self._in_lens),
                        "num_physical": m_phys,
                        "degrees": list(self.plan.degrees)}
                    self.plan_cache.store(pkey, meta, arrays)
            self._planned = planned
            self._reduce_fn = planned.make_reduce_fn(mesh)
            self._u_cap = planned.user_scatter.shape[1]
            autotune.memo_store(fp, (planned, self._reduce_fn, stats))
            return stats
        raise ValueError(f"unknown backend {self.backend!r}")

    # ------------------------------------------------------------------
    def reconfig_dead(self, dead: Optional[Set[int]]) -> None:
        """Incremental repair (device backend): swap the dead set without
        host re-planning.

        The frozen routing is dead-set-invariant — only the contribution
        weights and the first-alive read-back rows change — so this is
        ``PlannedSparseAllreduce.with_dead`` + one retrace of the reduce
        body, orders of magnitude cheaper than a fresh :meth:`config`
        (``benchmarks/bench_soak.py`` measures both).  Repaired plans are
        cached per dead set, so flip-flopping between failure sets (a
        supervisor's retry loop) retraces each at most once.

        Raises ``DeadLogicalNode`` when ``dead`` kills a whole replica
        group, *before* any state changes — the instance stays usable with
        its previous dead set, and the caller (``repro.resilience``) moves
        on to replan-over-survivors.  Afterwards ``config_cache`` reads
        ``"repair"``.
        """
        if self.backend != "device":
            raise ValueError("reconfig_dead() requires backend='device'")
        if self._planned is None:
            raise RuntimeError("call config() before reconfig_dead()")
        from .replication import first_alive_replicas
        # Validation first: a lost replica group must leave `self` intact.
        first_alive = first_alive_replicas(self.num_physical,
                                           self.replication, dead)
        key = frozenset(dead or ())
        cache = getattr(self, "_repair_cache", None)
        if cache is None:
            cache = self._repair_cache = {}
        hit = cache.get(key)
        if hit is None:
            planned = self._planned.with_dead(dead)
            hit = (planned, planned.make_reduce_fn(self._mesh_used))
            cache[key] = hit
        self._planned, self._reduce_fn = hit
        self._first_alive = first_alive
        self.dead = set(key) or None
        self._union_cache = {}       # union fns bake the dead set too
        self.config_cache = "repair"

    # ------------------------------------------------------------------
    def reduce(self, out_values: Sequence[np.ndarray]) -> List[np.ndarray]:
        """``out_values``: one array per *logical* node; with replication
        the values are staged onto every replica (dead / non-first replicas
        are zero-weighted on device) and each logical result is read back
        from its first alive replica."""
        if self.backend == "sim":
            return self._sim.reduce(out_values)
        import jax.numpy as jnp
        r, m_phys = self.replication, self.num_physical
        if self._staging is None:
            # Reusable host staging buffer + flat scatter coordinates
            # (precomputable: config froze the per-node lengths).  Repeated
            # same-shape reduces then pay one vectorized scatter instead of
            # a fresh np.zeros + per-node copy loop per call.
            vshape = (m_phys, self._u_cap) + \
                ((self.width,) if self.width > 1 else ())
            self._staging = np.zeros(vshape, np.float32)
            phys_lens = list(self._out_lens) * r
            self._stage_rows = np.repeat(np.arange(m_phys),
                                         np.asarray(phys_lens))
            self._stage_cols = np.concatenate(
                [np.arange(l, dtype=np.int64) for l in phys_lens])
        for n, v in enumerate(out_values):
            if len(v) != self._out_lens[n]:
                raise ValueError(
                    f"reduce: node {n} passed {len(v)} values, config "
                    f"declared {self._out_lens[n]}")
        flat = np.concatenate([np.asarray(v, np.float32).reshape(
            (-1,) + ((self.width,) if self.width > 1 else ()))
            for v in out_values], axis=0)
        if r > 1:
            flat = np.concatenate([flat] * r, axis=0)
        # cells beyond each node's out length stay zero across calls, so no
        # per-call clearing is needed either.
        self._staging[self._stage_rows, self._stage_cols] = flat
        out = np.asarray(self._reduce_fn(jnp.asarray(self._staging)))
        return [out[self._first_alive[n], : self._in_lens[n]]
                for n in range(self.num_nodes)]

    # ------------------------------------------------------------------
    def union_reduce(self, idx, val, out_capacity: int,
                     use_kernel: bool = False):
        """Gather-all union sum with dynamic indices (the paper's mini-batch
        mode) on a device mesh, honouring the ``merge`` and ``wire`` knobs
        (with ``wire="delta"`` results are bit-identical to ``"raw"``; the
        lossy modes trade bounded value error for wire bytes).

        idx: uint32 [num_nodes, C] *hashed, sorted*, SENTINEL-padded per-node
        indices; val: [num_nodes, C] or [num_nodes, C, W] — one chunk per
        *logical* node.  With ``replication=r`` the chunks are mirrored onto
        ``r * num_nodes`` physical mesh devices, ``contribution_weights``
        (for this instance's ``dead`` set) are applied inside shard_map, and
        the per-logical-node results are read back from each shard's first
        alive replica; raises ``DeadLogicalNode`` when a replica group is
        lost.  Returns (idx [num_nodes, out_capacity], val,
        overflow [num_nodes]) — every node gets the full union sum.
        Requires a mesh of ``num_nodes * replication`` devices.  The plan
        and compiled pipeline are cached per (shape, out_capacity,
        use_kernel, dead), so repeated same-shape calls pay tracing once.
        """
        import jax
        import jax.numpy as jnp

        from .allreduce import make_device_plan, run_union_allreduce
        from .replication import contribution_weights, first_alive_replicas
        from repro.launch.mesh import make_mesh
        r, m_phys = self.replication, self.num_physical
        if r != 1 or self.dead:
            contribution_weights(m_phys, r, self.dead)  # DeadLogicalNode
        idx = jnp.asarray(idx)
        val = jnp.asarray(val)
        if idx.shape[0] != self.num_nodes:
            raise ValueError(
                f"union_reduce: expected {self.num_nodes} logical chunks, "
                f"got {idx.shape[0]}")
        if r > 1:
            idx = jnp.tile(idx, (r,) + (1,) * (idx.ndim - 1))
            val = jnp.tile(val, (r,) + (1,) * (val.ndim - 1))
        key = (idx.shape, val.shape, val.dtype, out_capacity, use_kernel,
               frozenset(self.dead or ()), self.wire)
        fn = self._union_cache.get(key)
        if fn is not None:
            self.union_plan_stats["hits"] += 1
        else:
            self.union_plan_stats["misses"] += 1
            mesh = self.mesh
            if mesh is None:
                mesh = make_mesh((m_phys,), ("nodes",))
            axis = mesh.axis_names[0]
            dplan = make_device_plan(
                [(axis, m_phys)], {axis: self.plan.degrees},
                in_capacity=idx.shape[1], out_capacity=out_capacity,
                replication=r)
            fn = jax.jit(lambda i, v: run_union_allreduce(
                mesh, dplan, i, v, use_kernel=use_kernel, merge=self.merge,
                dead=self.dead, wire=self.wire))
            self._union_cache[key] = fn
        oi, ov, ovf = fn(idx, val)
        if r > 1:
            fa = first_alive_replicas(m_phys, r, self.dead)
            oi, ov, ovf = oi[fa], ov[fa], ovf[fa]
        return oi, ov, ovf

    # ------------------------------------------------------------------
    # Plan-reuse hooks (device backend).  :meth:`reduce` pays one host
    # staging + one device dispatch per call; iterative workloads that can
    # keep their state on device should instead compose the frozen plan
    # into their own jitted loop via these hooks — ``repro.graph.engine``
    # does exactly that (k rounds, one dispatch).
    # ------------------------------------------------------------------

    def planned_parts(self) -> Tuple["object", "object"]:
        """``(PlannedSparseAllreduce, mesh)`` bound at :meth:`config` time.

        Device backend only, after ``config``.  ``planned.reduce_on_device``
        is the shard_map body (per-device ``[u_cap(,W)] -> [uin_cap(,W)]``),
        ``planned.device_args()`` the iteration-invariant routing tensors —
        everything needed to embed the reduce inside a caller-owned
        shard_map / ``lax.scan`` without re-planning or re-tracing.
        """
        if self.backend != "device":
            raise ValueError("planned_parts() requires backend='device'")
        if self._planned is None:
            raise RuntimeError("call config() before planned_parts()")
        return self._planned, self._mesh_used

    @property
    def reduce_fn(self):
        """The raw jitted reduce callable (device backend, after config):
        ``[num_physical, u_cap(,W)] jnp array -> [num_physical, uin_cap(,W)]``.
        This is what :meth:`reduce` invokes after host-side staging; callers
        holding device-resident staged values can call it directly and skip
        the numpy round-trip."""
        if self._reduce_fn is None:
            raise RuntimeError(
                "reduce_fn requires backend='device' and a prior config()")
        return self._reduce_fn

    def staging_metadata(self) -> dict:
        """Static staging layout frozen by :meth:`config` (device backend):
        ``u_cap`` / ``uin_cap`` (per-device value capacities),
        ``out_lens`` / ``in_lens`` (per-logical-node valid lengths inside
        those capacities), ``first_alive`` (physical replica each logical
        result is read from) and ``num_physical``.  Everything a caller
        needs to build ``reduce_fn`` inputs / slice its outputs without
        private attribute access."""
        if self._planned is None:
            raise RuntimeError("call config() before staging_metadata()")
        return {
            "u_cap": self._planned.u_cap,
            "uin_cap": self._planned.uin_cap,
            "out_lens": list(self._out_lens),
            "in_lens": list(self._in_lens),
            "first_alive": list(self._first_alive),
            "num_physical": self.num_physical,
        }

    @property
    def stats(self) -> Optional[ReduceStats]:
        """Message-level :class:`ReduceStats` of the last :meth:`reduce`
        (sim backend only; the device backend returns modeled stats from
        :meth:`config`'s shadow sim instead)."""
        if self.backend == "sim" and self._sim is not None:
            return getattr(self._sim, "reduce_stats", None)
        return None
