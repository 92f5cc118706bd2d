"""Planned Sparse Allreduce: host-side ``config``, device-side ``reduce``.

This is the paper's property #2 (§I-B): *"Index calculations (configuration)
can be separated from value calculations and only computed once for problems
where the indices are fixed (e.g. PageRank iterations)."*

``config`` runs the message-level routing ONCE on host (numpy, via the
simulator's data structures), then freezes every routing decision into
static, padded gather/scatter index tensors.  ``reduce`` is then a pure
static-shape device program — gathers, ``all_to_all`` exchanges, and
scatter-adds inside shard_map — jitted once and reused every iteration with
new values.  Indices are never re-communicated (paper §IV-A: "vertex indices
are already hard-coded in the maps").
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .allreduce import DevicePlan
from .sparse_vec import HashPerm
from .simulator import SimSparseAllreduce
from .topology import ButterflyPlan
from repro.obs import scope


def _pad_gather(rows: List[np.ndarray], width: int) -> np.ndarray:
    """Stack ragged position rows into [len(rows), width], -1 padded."""
    out = np.full((len(rows), width), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, : len(r)] = r
    return out


@dataclasses.dataclass
class _LayerMaps:
    send_gather: np.ndarray    # [M, k, cap]  -> positions in current values
    merge_scatter: np.ndarray  # [M, k, cap]  -> positions in next values (or m_max)
    merged_size: int           # m_max (+1 slot used as drop bin)
    up_send_gather: np.ndarray  # [M, k, upcap] -> positions in my up array
    up_recv_scatter: np.ndarray  # [M, k, upcap] -> positions in my (layer-l) up array
    up_size: int


@dataclasses.dataclass
class PlannedSparseAllreduce:
    """Static-index sparse allreduce bound to a mesh (device backend only;
    the simulator analogue is ``SimSparseAllreduce``).

    Build with :func:`plan_sparse_allreduce` (the paper's ``config``) —
    host-side numpy, run once per index pattern.  Afterwards everything is
    static and reusable every iteration:

    * :meth:`reduce_on_device` — the shard_map *body*: per-device values
      ``[u_cap(,W)]`` in, per-device results ``[uin_cap(,W)]`` out.  Pure
      static-shape JAX, so it composes into larger jitted programs — in
      particular into a ``lax.scan`` iteration loop (see
      ``repro.graph.engine``, which fuses a local SpMV with this body to
      run k PageRank/HADI/spectral rounds in one dispatch).
    * :meth:`make_reduce_fn` — a standalone jitted host entry point
      (``[M, u_cap(,W)] -> [M, uin_cap(,W)]``) for per-call use.
    * :meth:`device_args` / :meth:`arg_specs` — the frozen routing tensors
      (and their PartitionSpecs) that ``reduce_on_device`` consumes; pass
      them through your own shard_map sharded over the plan axes.  They are
      iteration-invariant: hoist them out of any scan.

    Amortization contract: one ``plan_sparse_allreduce`` call amortizes
    over arbitrarily many ``reduce_on_device`` / ``reduce_fn`` invocations
    as long as the index pattern (and mesh) is unchanged; values may differ
    freely.  Width ``W`` (``value_width``) is frozen at plan time.
    """

    dplan: DevicePlan
    perm: HashPerm
    width: int
    # host-side padded routing tensors (converted lazily to device arrays)
    user_scatter: np.ndarray        # [M, u_cap] user slot -> sorted slot
    sorted_size: int
    layers: List[_LayerMaps]
    bottom_gather: np.ndarray       # [M, q_cap] positions into bottom values
    bottom_hit: np.ndarray          # [M, q_cap] bool
    user_gather: np.ndarray         # [M, uin_cap] sorted-in slot per user slot
    in_user_len: int
    # r-way replication (paper §V): per-physical-node contribution weight
    # (1.0 on each logical shard's first alive replica, 0.0 elsewhere),
    # applied to the values inside shard_map.  None when not replicated.
    weights: Optional[np.ndarray] = None
    # Trace-count regression hook: ``reduce_on_device`` runs only while a
    # surrounding program is being traced, so this counts (re)traces of the
    # reduce body.  The autotuner's plan memo (``repro.core.autotune``)
    # asserts it stays flat across plan-cache hits.
    trace_count: int = dataclasses.field(default=0, compare=False)

    # ---------------------------------------------------------------------
    @property
    def u_cap(self) -> int:
        """Per-device *outbound* value capacity: ``reduce_on_device`` takes
        ``[u_cap(,W)]`` (node n's first ``len(out_indices[n])`` slots are
        its user values, the rest zero padding)."""
        return int(self.user_scatter.shape[1])

    @property
    def uin_cap(self) -> int:
        """Per-device *inbound* capacity: ``reduce_on_device`` returns
        ``[uin_cap(,W)]`` (node n's first ``len(in_indices[n])`` slots are
        the reduced values in its requested order, the rest zeros)."""
        return int(self.in_user_len)

    @property
    def depth(self) -> int:
        """Butterfly depth — each reduce runs ``depth`` down + ``depth`` up
        ``all_to_all`` collectives (the per-round sync count)."""
        return len(self.layers)

    @property
    def q_cap(self) -> int:
        """Per-device *bottom* capacity: :meth:`reduce_down_on_device`
        returns (and :meth:`reduce_up_on_device` takes) ``[q_cap(,W)]`` —
        the root-layer partial sums each node owns between the two
        halves."""
        return int(self.bottom_gather.shape[1])

    # ---------------------------------------------------------------------
    def device_args(self):
        """Routing tensors as jnp arrays, ordered for reduce_on_device."""
        args = [jnp.asarray(self.user_scatter)]
        if self.weights is not None:
            args.insert(0, jnp.asarray(self.weights))
        for L in self.layers:
            args += [jnp.asarray(L.send_gather), jnp.asarray(L.merge_scatter),
                     jnp.asarray(L.up_send_gather), jnp.asarray(L.up_recv_scatter)]
        args += [jnp.asarray(self.bottom_gather), jnp.asarray(self.bottom_hit),
                 jnp.asarray(self.user_gather)]
        return args

    def arg_specs(self):
        """PartitionSpecs matching :meth:`device_args`, sharded over the
        plan axes (pass through your own shard_map's in_specs)."""
        from jax.sharding import PartitionSpec as P
        axes = tuple(n for n, _ in self.dplan.axes)
        n = len(self.device_args())
        return tuple(P(axes if len(axes) > 1 else axes[0]) for _ in range(n))

    # ---------------------------------------------------------------------
    def _routing_parts(self, routing):
        """Name + squeeze the flat ``routing`` tuple both halves consume.

        Routing tensors arrive sharded with a leading per-device dim of
        size 1 on each plan axis; returns ``(weights, user_scatter,
        per_layer, bottom_gather, bottom_hit, user_gather)`` with
        ``per_layer`` a list of ``(send_gather, merge_scatter,
        up_send_gather, up_recv_scatter)`` tuples."""
        nax = len(self.dplan.axes)

        def sq(a):
            return a.reshape(a.shape[nax:])

        it = iter(routing)
        weights = sq(next(it)) if self.weights is not None else None
        user_scatter = sq(next(it))
        per_layer = [tuple(sq(next(it)) for _ in range(4))
                     for _ in self.layers]
        return (weights, user_scatter, per_layer,
                sq(next(it)), sq(next(it)), sq(next(it)))

    def reduce_on_device(self, values: jax.Array, *routing) -> jax.Array:
        """shard_map body: values [u_cap(,W)] on this device -> [uin_cap(,W)].

        Composition of the two halves — ``depth`` down ``all_to_all``
        stages then ``depth`` up stages back-to-back (the bulk-synchronous
        schedule).  Overlapped callers (``repro.graph.engine`` with
        ``overlap=True``) call :meth:`reduce_down_on_device` /
        :meth:`reduce_up_on_device` directly so independent compute can sit
        between the halves; both schedules run the identical op sequence,
        so results are bitwise equal (tests/test_overlap.py).
        """
        return self.reduce_up_on_device(
            self.reduce_down_on_device(values, *routing), *routing)

    def reduce_down_on_device(self, values: jax.Array, *routing) -> jax.Array:
        """Bottom half of the reduce: user values ``[u_cap(,W)]`` ->
        root-layer partial sums ``[q_cap(,W)]`` (``depth`` down
        ``all_to_all`` stages + per-stage scatter-add merges).  Counts one
        reduce trace (``trace_count``); the up half does not, so a full
        reduce nets exactly one however it is scheduled."""
        self.trace_count += 1
        (weights, user_scatter, per_layer, bottom_gather, bottom_hit,
         _user_gather) = self._routing_parts(routing)
        if weights is not None:
            # replica contribution weight (scalar per device, paper §V)
            values = values * weights.astype(values.dtype)
        W = values.shape[-1] if values.ndim > 1 else None

        def zeros(n):
            return jnp.zeros((n,) if W is None else (n, W), values.dtype)

        # coalesce user values onto sorted slots (+1 drop bin for padding)
        cur = zeros(self.sorted_size + 1).at[user_scatter].add(values)[:-1]

        stages = self.dplan.stages
        for l, L in enumerate(self.layers):
            send_g, merge_s, _up_g, _up_s = per_layer[l]
            k, cap = send_g.shape[0], send_g.shape[1]
            g = list(map(list, stages[l].axis_index_groups))
            with scope(f"planned/down{l}"):
                safe = jnp.maximum(send_g, 0)
                picked = cur[safe] * (send_g >= 0)[
                    (...,) + (None,) * (values.ndim - 1)]
                recv = lax.all_to_all(picked, stages[l].axis_name,
                                      split_axis=0, concat_axis=0,
                                      axis_index_groups=g)
                nxt = zeros(L.merged_size + 1)
                nxt = nxt.at[merge_s.reshape((-1,))].add(
                    recv.reshape((k * cap,) + recv.shape[2:]))
                cur = nxt[:-1]

        return cur[jnp.maximum(bottom_gather, 0)] \
            * bottom_hit[(...,) + (None,) * (values.ndim - 1)]

    def reduce_up_on_device(self, up: jax.Array, *routing) -> jax.Array:
        """Top half of the reduce: root-layer partials ``[q_cap(,W)]``
        (from :meth:`reduce_down_on_device`) -> requested values
        ``[uin_cap(,W)]`` (``depth`` up ``all_to_all`` return stages in
        reverse layer order + the final user gather)."""
        (_weights, _user_scatter, per_layer, _bottom_gather, _bottom_hit,
         user_gather) = self._routing_parts(routing)
        ndim = up.ndim
        W = up.shape[-1] if ndim > 1 else None

        def zeros(n):
            return jnp.zeros((n,) if W is None else (n, W), up.dtype)

        for l in reversed(range(len(self.layers))):
            _send_g, _merge_s, up_g, up_s = per_layer[l]
            k, cap = up_g.shape[0], up_g.shape[1]
            g = list(map(list, self.dplan.stages[l].axis_index_groups))
            with scope(f"planned/up{l}"):
                safe = jnp.maximum(up_g, 0)
                picked = up[safe] * (up_g >= 0)[(...,) + (None,) * (ndim - 1)]
                recv = lax.all_to_all(picked, self.dplan.stages[l].axis_name,
                                      split_axis=0, concat_axis=0,
                                      axis_index_groups=g)
                nxt = zeros(self.layers[l].up_size + 1)
                nxt = nxt.at[up_s.reshape((-1,))].set(
                    recv.reshape((k * cap,) + recv.shape[2:]), mode="drop")
                up = nxt[:-1]

        return up[jnp.maximum(user_gather, 0)] \
            * (user_gather >= 0)[(...,) + (None,) * (ndim - 1)]

    # ---------------------------------------------------------------------
    def with_dead(self, dead=None) -> "PlannedSparseAllreduce":
        """Incremental repair: the same frozen routing with a new dead set.

        Only the per-device contribution weights depend on ``dead`` — the
        gather/scatter routing tensors are dead-set-invariant (every device
        receives the full union, paper §V) — so repairing a plan after a
        replica-absorbed failure is a ``dataclasses.replace`` of the
        weights, not a host replan.  The result needs one retrace (weights
        are baked into the jitted body as constants), hence the fresh
        ``trace_count``.  Raises ``DeadLogicalNode`` when ``dead`` kills a
        whole replica group — callers wanting to continue must replan over
        survivors instead (``repro.resilience``).
        """
        from .replication import contribution_weights
        weights = contribution_weights(self.dplan.logical.num_nodes,
                                       self.dplan.replication, dead)
        return dataclasses.replace(self, weights=weights, trace_count=0)

    # ---------------------------------------------------------------------
    def make_reduce_fn(self, mesh: jax.sharding.Mesh):
        """Jitted host entry: values [M, u_cap(,W)] -> [M, uin_cap(,W)]."""
        from jax.sharding import PartitionSpec as P

        from repro.compat import shard_map
        shape = tuple(s for _, s in self.dplan.axes)
        axes = tuple(n for n, _ in self.dplan.axes)
        nax = len(shape)
        spec = P(*axes)
        routing = self.device_args()

        def body(v, *r):
            v = v.reshape(v.shape[nax:])
            out = self.reduce_on_device(v, *r)
            return out.reshape((1,) * nax + out.shape)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(spec,) + tuple(spec for _ in routing),
                       out_specs=spec, check_vma=False)

        def run(values: jax.Array) -> jax.Array:
            v = values.reshape(shape + values.shape[1:])
            out = fn(v, *routing)
            m = math.prod(shape)
            return out.reshape((m,) + out.shape[nax:])

        return jax.jit(run)


# ---------------------------------------------------------------------------
# config: run host routing once, freeze into padded tensors
# ---------------------------------------------------------------------------

def plan_sparse_allreduce(dplan: DevicePlan,
                          out_indices: Sequence[np.ndarray],
                          in_indices: Sequence[np.ndarray],
                          perm: Optional[HashPerm] = None,
                          width: int = 1,
                          dead=None) -> PlannedSparseAllreduce:
    """The paper's ``config`` call: indices in, frozen routing out.

    For r-way replicated plans (``make_device_plan(replication=r)``,
    paper §V) ``out_indices`` / ``in_indices`` are the *logical* per-shard
    index lists (``dplan.num_logical`` of them); routing is frozen for all
    ``r * num_logical`` physical replicas and ``dead`` physical node ids
    are masked via ``contribution_weights`` applied to the values inside
    shard_map.  Raises ``DeadLogicalNode`` when a whole replica group is
    dead.  Cost curves: benchmarks/bench_fault_tolerance.py.
    """
    perm = perm if perm is not None else HashPerm.make(0)
    weights = None
    if dplan.replication > 1 or dead:
        from .replication import contribution_weights
        weights = contribution_weights(dplan.logical.num_nodes,
                                       dplan.replication, dead)
        if len(out_indices) != dplan.num_logical:
            raise ValueError(
                f"replicated plan expects {dplan.num_logical} logical index "
                f"lists, got {len(out_indices)}")
        out_indices = list(out_indices) * dplan.replication
        in_indices = list(in_indices) * dplan.replication
    sim = SimSparseAllreduce(dplan.logical, perm=perm, value_width=width)
    sim.config(out_indices, in_indices)
    plan, m = dplan.logical, dplan.logical.num_nodes
    didx = sim._down_idx_cache  # per-layer sorted idx arrays

    u_cap = max(len(u) for u in sim.out_user_to_sorted) or 1
    sorted_size = max(len(s) for s in sim.out_sorted) or 1
    user_scatter = np.full((m, u_cap), sorted_size, np.int32)  # drop bin
    for n in range(m):
        user_scatter[n, : len(sim.out_user_to_sorted[n])] = \
            sim.out_user_to_sorted[n]

    layers: List[_LayerMaps] = []
    for l in range(plan.depth):
        k = plan.degrees[l]
        # send pieces: node n -> digit t: slice cuts[t]:cuts[t+1] of cur
        send_rows, merge_rows = [], []
        cap = 0
        cuts_all = []
        for n in range(m):
            cuts = np.searchsorted(didx[l][n].astype(np.uint64),
                                   plan.edges_at(n, l).astype(np.uint64))
            cuts_all.append(cuts)
            cap = max(cap, int(np.max(cuts[1:] - cuts[:-1])))
        merged_size = max(len(didx[l + 1][n]) for n in range(m)) or 1
        send_gather = np.full((m, k, cap), -1, np.int32)
        merge_scatter = np.full((m, k, cap), merged_size, np.int32)
        for n in range(m):
            members = plan.group_members(n, l)
            t_self = members.index(n)
            cuts = cuts_all[n]
            for t in range(k):
                ln = cuts[t + 1] - cuts[t]
                send_gather[n, t, :ln] = np.arange(cuts[t], cuts[t + 1])
            # merge: received piece from member with digit t = that member's
            # slice at t_self; its position in my merged array = inv map
            src_slices, inv, uniq = sim.down_maps[l][n]
            for t in range(k):
                seg = inv[src_slices[t]:src_slices[t + 1]]
                merge_scatter[n, t, : len(seg)] = seg
        # up phase maps
        upcap = 0
        for n in range(m):
            for t in range(k):
                upcap = max(upcap, len(sim.ret_pos[l][n][t]))
        upcap = max(upcap, 1)
        up_size = max(len(sim.in_at[l][n]) for n in range(m)) or 1
        up_send_gather = np.full((m, k, upcap), -1, np.int32)
        up_recv_scatter = np.full((m, k, upcap), up_size, np.int32)
        for n in range(m):
            members = plan.group_members(n, l)
            digit_of = {mem: t for t, mem in enumerate(members)}
            t_self = digit_of[n]
            # as sender: to peer with digit t, send values for that peer's
            # request piece, positions in MY layer-(l+1) up array
            for t, mem in enumerate(members):
                pos = sim.ret_pos[l][mem][t_self]  # mem requested from me
                up_send_gather[n, t, : len(pos)] = pos
            # as receiver: piece from member with digit t lands at my cuts
            own_idx = sim.in_at[l][n]
            cuts = np.searchsorted(own_idx.astype(np.uint64),
                                   plan.edges_at(n, l).astype(np.uint64))
            for t in range(k):
                ln = cuts[t + 1] - cuts[t]
                up_recv_scatter[n, t, :ln] = np.arange(cuts[t], cuts[t + 1])
        layers.append(_LayerMaps(send_gather=send_gather,
                                 merge_scatter=merge_scatter,
                                 merged_size=merged_size,
                                 up_send_gather=up_send_gather,
                                 up_recv_scatter=up_recv_scatter,
                                 up_size=up_size))

    q_cap = max(len(p) for p in sim.bottom_pos) or 1
    bottom_gather = np.full((m, q_cap), -1, np.int32)
    bottom_hit = np.zeros((m, q_cap), bool)
    for n in range(m):
        bottom_gather[n, : len(sim.bottom_pos[n])] = sim.bottom_pos[n]
        bottom_hit[n, : len(sim.bottom_hit[n])] = sim.bottom_hit[n]

    uin_cap = max(len(u) for u in sim.in_sorted_to_user) or 1
    user_gather = np.full((m, uin_cap), -1, np.int32)
    for n in range(m):
        user_gather[n, : len(sim.in_sorted_to_user[n])] = \
            sim.in_sorted_to_user[n]

    # Normalize per-layer pad sizes: values arrays must have one static size
    # per layer across devices — we already took maxima; per-device shorter
    # content is padded with drop bins / -1.
    return PlannedSparseAllreduce(
        dplan=dplan, perm=perm, width=width,
        user_scatter=user_scatter, sorted_size=sorted_size, layers=layers,
        bottom_gather=bottom_gather, bottom_hit=bottom_hit,
        user_gather=user_gather, in_user_len=uin_cap, weights=weights)
