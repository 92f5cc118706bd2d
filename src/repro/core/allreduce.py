"""TPU-native Sparse Allreduce: nested heterogeneous butterfly over shard_map.

The paper's point-to-point socket schedule maps onto mesh collectives:

  * one butterfly layer of degree k  ==  ``lax.all_to_all`` within
    ``axis_index_groups`` of size k along the data-parallel mesh axis
    (down / scatter-reduce), and ``lax.all_gather`` within the same groups
    in reverse order (up / allgather) — the paper's *nested* pattern;
  * the hash-permuted sorted-range partition becomes a static-shape
    ``bucket_partition`` (contiguous slabs of the sorted chunk);
  * the tree-merge sum becomes sort + segment-compact (MXU-friendly
    one-hot-matmul kernel in kernels/segment_compact.py).

SPMD needs static shapes, so every stage has a capacity derived from the
requested output capacity plus a balance slack; overflow is *counted* and
returned (the same contract as MoE token dropping).  The paper's hash
permutation is exactly what makes these capacities safe.

Dense baselines (ring / binary butterfly / hierarchical heterogeneous
butterfly) live here too — they are the paper's §II comparison points and
the beyond-paper dense gradient path.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .sparse_vec import (SENTINEL, SparseChunk, bucket_partition,
                         concat_sorted_groups, segment_compact, sort_chunk)
from .topology import ButterflyPlan, check_wire
from repro.obs import scope


# ---------------------------------------------------------------------------
# Device-side plan: stages spanning one or more mesh axes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One butterfly layer bound to a mesh axis."""
    axis_name: str
    degree: int
    axis_index_groups: Tuple[Tuple[int, ...], ...]
    bucket_capacity: int
    merged_capacity: int


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """Butterfly plan bound to mesh axes, with static capacities.

    ``axes``: ordered [(axis_name, axis_size)], most-significant first
    (e.g. [("pod", 2), ("data", 16)]).  ``degrees_per_axis`` factorizes each
    axis; the concatenated degree sequence is the logical ButterflyPlan over
    prod(sizes) nodes.  Edges arrays are host-precomputed per logical node
    and passed into shard_map sharded over the same axes.

    ``replication`` > 1 marks the plan as r-way replicated (paper §V):
    the ``num_nodes`` physical devices host ``num_nodes / r`` logical
    shards, replica j of shard i at physical id ``i + j * num_logical``
    (``repro.core.replication.replica_groups``), and stage 0 is the
    replica-merge layer — node ids are mixed-radix with digit 0 most
    significant, so prepending degree r makes the stage-0 groups exactly
    the replica groups.
    """

    axes: Tuple[Tuple[str, int], ...]
    stages: Tuple[Stage, ...]
    logical: ButterflyPlan
    in_capacity: int
    out_capacity: int
    replication: int = 1

    @property
    def num_nodes(self) -> int:
        """Physical node count (= prod of the bound mesh-axis sizes)."""
        return self.logical.num_nodes

    @property
    def num_logical(self) -> int:
        """Logical shard count (== num_nodes unless replicated)."""
        return self.logical.num_nodes // self.replication

    @property
    def slots_received(self) -> int:
        """Rows one node receives from the other nodes in one union
        allreduce, as the static capacities fix them: (k - 1) buckets of
        ``bucket_capacity`` at each down stage, and (k - 1) gathered chunks
        at each up stage, a chunk being the last stage's merged capacity
        times the degrees of the up stages already gathered."""
        down = sum((st.degree - 1) * st.bucket_capacity for st in self.stages)
        up, chunk = 0, self.stages[-1].merged_capacity if self.stages else 0
        for st in reversed(self.stages):
            up += (st.degree - 1) * chunk
            chunk *= st.degree
        return down + up

    def replica_groups(self):
        """[[physical ids] per logical shard] (see core.replication)."""
        from .replication import replica_groups
        return replica_groups(self.num_nodes, self.replication)

    def edges_arrays(self) -> List[np.ndarray]:
        """Per-stage [*axis_sizes, k_l + 1] uint32 range-edge tensors."""
        out = []
        shape = tuple(s for _, s in self.axes)
        for l, st in enumerate(self.stages):
            e = self.logical.all_edges(l)                       # [M, k+1] int64
            e = np.minimum(e, (1 << 32) - 1).astype(np.uint32)
            out.append(e.reshape(shape + (st.degree + 1,)))
        return out


def make_device_plan(axes: Sequence[Tuple[str, int]],
                     degrees_per_axis: dict,
                     in_capacity: int,
                     out_capacity: int,
                     slack: float = 2.0,
                     replication: int = 1) -> DevicePlan:
    """Bind a heterogeneous butterfly to mesh axes with static capacities.

    Capacity schedule: stage l buckets hold ``ceil(m_{l-1}/k * slack)``
    entries; merged chunks hold ``min(k*c_l, ceil(out_capacity * slack /
    prod(k_1..k_l)))`` — lossless when the hash permutation balances ranges
    (paper §III-A) and ``out_capacity`` covers the global union.

    ``replication=r`` builds the r-way replicated layout (paper §V):
    ``degrees_per_axis`` then gives the *logical* degree sequence (over
    ``size / r`` shards for the first axis) and the physical plan prepends
    a degree-r replica-merge stage to the first (most significant) axis,
    whose groups are ``replication.replica_groups(prod(sizes), r)``.
    Apply ``contribution_weights`` to the values fed in (``dead=`` on
    :func:`run_union_allreduce`) so each shard is counted exactly once.
    """
    if replication < 1:
        raise ValueError(f"replication must be >= 1, got {replication}")
    if replication > 1:
        name0, size0 = axes[0]
        if size0 % replication:
            raise ValueError(
                f"first axis {name0}={size0} not divisible by "
                f"r={replication}")
        base = tuple(degrees_per_axis.get(
            name0, (size0 // replication,) if size0 > replication else ()))
        degrees_per_axis = dict(degrees_per_axis)
        degrees_per_axis[name0] = (replication,) + base
    degrees: List[int] = []
    for name, size in axes:
        d = tuple(degrees_per_axis.get(name, (size,)))
        if math.prod(d) != size:
            raise ValueError(f"axis {name}: prod{d} != {size}")
        degrees.extend(d)
    m = math.prod(s for _, s in axes)
    logical = ButterflyPlan(m, tuple(degrees))

    # axis-local groups per stage
    stages: List[Stage] = []
    li = 0
    m_prev = in_capacity
    prod_k = 1
    for name, size in axes:
        sub = ButterflyPlan(size, tuple(degrees_per_axis.get(name, (size,))))
        for sl in range(sub.depth):
            k = sub.degrees[sl]
            groups = tuple(tuple(g) for g in sub.axis_index_groups(sl))
            cap = _round8(int(math.ceil(m_prev / k * slack)))
            prod_k *= k
            merged = min(k * cap,
                         _round8(int(math.ceil(out_capacity * slack / prod_k))))
            merged = max(merged, 8)
            stages.append(Stage(axis_name=name, degree=k,
                                axis_index_groups=groups,
                                bucket_capacity=cap, merged_capacity=merged))
            m_prev = merged
            li += 1
    return DevicePlan(axes=tuple(axes), stages=tuple(stages), logical=logical,
                      in_capacity=in_capacity, out_capacity=out_capacity,
                      replication=replication)


def _round8(x: int) -> int:
    return max(8, ((x + 7) // 8) * 8)


def shape_bucket(n: int, floor: int = 8) -> int:
    """Round a capacity up to the next power of two (at least ``floor``).

    Serving-tier plan resolution (``repro.serve.dispatch``): continuous
    batching churns the per-step unique-index count, and every distinct
    ``union_reduce`` capacity is a distinct compiled pipeline in
    ``SparseAllreduce._union_cache``.  Bucketing capacities to powers of
    two bounds the cache at O(log range) entries, so after warmup nearly
    every step is a plan-cache hit (benchmarks/bench_serve.py reports the
    hit rate; acceptance floor 0.8)."""
    if n < 0:
        raise ValueError(f"shape_bucket: capacity must be >= 0, got {n}")
    if floor < 1:
        raise ValueError(f"shape_bucket: floor must be >= 1, got {floor}")
    b = int(floor)
    while b < n:
        b <<= 1
    return b


# Per-layer merge strategies for the union allreduce (see
# sparse_allreduce_union docstring; "fused"/"banded" are the Pallas modes
# of repro.kernels.ops.merge_sorted_runs).
MERGE_MODES = ("sort", "fused", "banded")


# ---------------------------------------------------------------------------
# The primitive: fused config-reduce with gather-all (union) semantics.
# Runs INSIDE shard_map.  (The paper's mini-batch mode: dynamic indices.)
# ---------------------------------------------------------------------------

def sparse_allreduce_union(chunk: SparseChunk, plan: DevicePlan,
                           edges: Sequence[jax.Array],
                           use_kernel: bool = False,
                           merge: str = "sort",
                           weight: Optional[jax.Array] = None,
                           wire: str = "raw"
                           ) -> Tuple[SparseChunk, jax.Array]:
    """Nested butterfly sparse allreduce; every node gets the full union sum.

    ``chunk``: this device's sorted SparseChunk (hashed indices).
    ``edges``: per-stage range-edge arrays, each shaped [1,...,1, k_l+1]
    after shard_map slicing — i.e. this device's own edges.
    ``merge`` selects the per-layer merge of the k sorted runs arriving at
    each butterfly layer: ``"sort"`` concatenates and fully re-sorts before
    segment-compacting; ``"fused"`` rank-merges the already-sorted runs,
    compacts duplicates, and scatter-adds in one pass via the Pallas
    pipeline in ``repro.kernels.ops.merge_sorted_runs`` (interpret-mode
    fallback off-TPU); ``"banded"`` is the same pipeline with both kernels
    band-limited by the sortedness bound (frontier-only compare tiles,
    ceil(k*bm/bk)+1 scatter tiles per output tile — see
    ``kernels.costmodel``).  All three produce identical results.
    ``weight`` (r-way replicated plans, paper §V): this device's scalar
    ``contribution_weights`` entry — 1.0 on the first alive replica of each
    logical shard, 0.0 elsewhere — multiplied into the values before the
    first layer so every shard's sum is taken from exactly one replica.
    Indices still flow from every replica (zeros merge away bit-exactly),
    so the union is identical to the fault-free non-replicated result.
    ``wire`` picks the on-wire payload encoding (``topology.WIRE_MODES``;
    codecs in ``repro.kernels.wirecodec``): every collective then carries
    bit-packed index offsets instead of uint32 words, and — for the lossy
    modes — bf16 or per-row int8 values, decoded against the statically
    known stage subrange base on the receiving side (down: this device's
    bucket; up: gather row t covers subrange t).  ``"delta"`` is exactly
    lossless, so its result is bit-identical to ``"raw"``; for the fused
    merge modes the int8 dequantization rides inside the scatter kernel
    (``merge_sorted_runs(row_scale=...)``) so wire payloads are never
    widened in memory.
    Returns (union chunk of capacity ``out_capacity`` per device replica,
    overflow count — entries dropped to capacity anywhere in the network).
    """
    if merge not in MERGE_MODES:
        raise ValueError(f"merge must be one of {MERGE_MODES}, got {merge!r}")
    check_wire(wire)
    if weight is not None:
        w = weight.reshape(()).astype(chunk.val.dtype)
        chunk = SparseChunk(idx=chunk.idx, val=chunk.val * w)
    overflow = jnp.zeros((), jnp.int32)
    compute_dtype = chunk.val.dtype
    if wire != "raw":
        from repro.kernels import wirecodec as _wc
        widths = _wc.stage_index_bits(plan)
        strides = _wc.stage_strides(plan)

    # ---- down: scatter-reduce through the layers --------------------------
    for l, st in enumerate(plan.stages):
        e = edges[l].reshape((-1,))[-(st.degree + 1):]
        groups = list(map(list, st.axis_index_groups))
        with scope(f"union/down{l}/bucket"):
            buckets, ovf = bucket_partition(chunk, e, st.degree,
                                            st.bucket_capacity)
            overflow = overflow + ovf
            scale = None
            if wire == "raw":
                send_idx, send_val = buckets.idx, buckets.val
            else:
                # Bucket d covers [e[d], e[d+1]); ship offsets from e[d].
                send_idx = _wc.pack_indices(buckets.idx,
                                            e[:st.degree].astype(jnp.uint32),
                                            widths[l])
                send_val = buckets.val
                if wire == "delta+bf16":
                    send_val = send_val.astype(jnp.bfloat16)
                elif wire == "delta+int8ef":
                    send_val, scale = _wc.quant8_rows(send_val)
        with scope(f"union/down{l}/exchange"):
            r_idx = lax.all_to_all(send_idx, st.axis_name, split_axis=0,
                                   concat_axis=0, axis_index_groups=groups)
            r_val = lax.all_to_all(send_val, st.axis_name, split_axis=0,
                                   concat_axis=0, axis_index_groups=groups)
            r_scale = None
            if scale is not None:
                r_scale = lax.all_to_all(scale, st.axis_name, split_axis=0,
                                         concat_axis=0,
                                         axis_index_groups=groups)
            if wire != "raw":
                # Every received row is a bucket for *this* device's
                # subrange, whose base is e[j] with j = our position in the
                # stage group (group members share identical stage-l edges).
                j = (lax.axis_index(st.axis_name) // strides[l]) % st.degree
                base = jnp.broadcast_to(e[j].astype(jnp.uint32), (st.degree,))
                r_idx = _wc.unpack_indices(r_idx, base, st.bucket_capacity,
                                           widths[l])
        with scope(f"union/down{l}/merge"):
            if merge in ("fused", "banded"):
                from repro.kernels import ops as _kops
                chunk, movf = _kops.merge_sorted_runs(
                    r_idx, r_val, st.merged_capacity, mode=merge,
                    row_scale=r_scale,
                    out_dtype=compute_dtype if wire != "raw" else None)
                overflow = overflow + movf
            else:
                if r_scale is not None:
                    r_val = _wc.dequant8_rows(r_val, r_scale)
                r_val = r_val.astype(compute_dtype)
                cat = concat_sorted_groups(r_idx, r_val)
                from .sparse_vec import compact_overflow
                overflow = overflow + compact_overflow(cat, st.merged_capacity)
                chunk = segment_compact(cat, st.merged_capacity,
                                        use_kernel=use_kernel)

    # ---- up: allgather back through the same nodes (nested) ---------------
    for li in range(len(plan.stages) - 1, -1, -1):
        st = plan.stages[li]
        g = list(map(list, st.axis_index_groups))
        with scope(f"union/up{li}/gather"):
            if wire == "raw":
                idx = lax.all_gather(chunk.idx, st.axis_name,
                                     axis_index_groups=g, axis=0, tiled=True)
                val = lax.all_gather(chunk.val, st.axis_name,
                                     axis_index_groups=g, axis=0, tiled=True)
            else:
                # The sender's chunk covers its own stage-li subrange [e[j],
                # e[j+1]); after the gather, row t covers subrange t of the
                # group-shared edges, so both bases are static knowledge.
                k = st.degree
                e = edges[li].reshape((-1,))[-(k + 1):]
                j = (lax.axis_index(st.axis_name) // strides[li]) % k
                packed = _wc.pack_indices(chunk.idx[None, :],
                                          e[j].astype(jnp.uint32)[None],
                                          widths[li])[0]
                words = lax.all_gather(packed, st.axis_name,
                                       axis_index_groups=g, axis=0,
                                       tiled=True).reshape((k, -1))
                idx = _wc.unpack_indices(words, e[:k].astype(jnp.uint32),
                                         chunk.capacity, widths[li]
                                         ).reshape((-1,))
                if wire == "delta":
                    val = lax.all_gather(chunk.val, st.axis_name,
                                         axis_index_groups=g, axis=0,
                                         tiled=True)
                elif wire == "delta+bf16":
                    val = lax.all_gather(chunk.val.astype(jnp.bfloat16),
                                         st.axis_name, axis_index_groups=g,
                                         axis=0, tiled=True
                                         ).astype(compute_dtype)
                else:
                    q, s = _wc.quant8_rows(chunk.val[None])
                    gq = lax.all_gather(q[0], st.axis_name,
                                        axis_index_groups=g, axis=0,
                                        tiled=True)
                    gs = lax.all_gather(s, st.axis_name, axis_index_groups=g,
                                        axis=0, tiled=True)  # [k] row scales
                    per = jnp.repeat(gs.astype(jnp.float32), chunk.capacity)
                    val = (gq.astype(jnp.float32)
                           * per[(...,) + (None,) * (gq.ndim - 1)]
                           ).astype(compute_dtype)
            # concat of sorted disjoint ranges
            chunk = SparseChunk(idx=idx, val=val)

    # Trim/pad to the advertised out capacity (sorted already).
    if chunk.capacity != plan.out_capacity:
        with scope("union/trim"):
            chunk = _trim_sorted(chunk, plan.out_capacity)
    return chunk, overflow


def _trim_sorted(chunk: SparseChunk, cap: int) -> SparseChunk:
    """Keep the first ``cap`` *valid* rows of a concat-of-sorted-ranges chunk.

    The concatenation of disjoint sorted ranges is globally sorted except for
    interleaved sentinel padding; compact valid rows to the front first.
    """
    valid = chunk.valid_mask()
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    c = chunk.capacity
    dest = jnp.where(valid, pos, c)
    out_idx = jnp.full((max(cap, 1),), SENTINEL, jnp.uint32)
    out_idx = out_idx.at[dest].set(chunk.idx, mode="drop")
    vshape = (cap,) + chunk.val.shape[1:]
    out_val = jnp.zeros(vshape, chunk.val.dtype)
    mask = valid[(...,) + (None,) * (chunk.val.ndim - 1)]
    out_val = out_val.at[dest].set(jnp.where(mask, chunk.val, 0), mode="drop")
    return SparseChunk(idx=out_idx, val=out_val)


# ---------------------------------------------------------------------------
# Dense baselines (paper §II) — run inside shard_map
# ---------------------------------------------------------------------------

def dense_allreduce_ring(x: jax.Array, axis_name) -> jax.Array:
    """Stock psum — XLA lowers to (bidirectional) ring; the round-robin
    analogue and the baseline every framework uses."""
    return lax.psum(x, axis_name)


def dense_allreduce_hierarchical(x: jax.Array, plan: DevicePlan) -> jax.Array:
    """Heterogeneous-degree hierarchical dense allreduce (beyond-paper dense
    path): reduce-scatter down the butterfly layers, all-gather back up.
    Requires x.shape[0] divisible by the total butterfly size."""
    for st in plan.stages:
        g = list(map(list, st.axis_index_groups))
        x = lax.psum_scatter(x, st.axis_name, scatter_dimension=0,
                             axis_index_groups=g, tiled=True)
    for st in reversed(plan.stages):
        g = list(map(list, st.axis_index_groups))
        x = lax.all_gather(x, st.axis_name, axis_index_groups=g, axis=0,
                           tiled=True)
    return x


def dense_allreduce_hierarchical_bucketed(
        xs: Sequence[jax.Array], plan: DevicePlan) -> List[jax.Array]:
    """:func:`dense_allreduce_hierarchical` over a list of buckets with a
    **stage-major** issue order: every bucket's stage-``l`` exchange is
    issued before any bucket's stage-``l+1`` (ARCHITECTURE.md "Overlap &
    scheduling").  With B buckets of depth D the lowered collective
    sequence is D runs of B ``reduce_scatter`` ops followed by D runs of B
    ``all_gather`` ops (reversed stage order) — the shape that lets XLA's
    latency-hiding scheduler slide independent compute between a bucket's
    issue and its consumption, instead of the one monolithic
    back-to-back chain the single-tensor path produces.

    Both collectives are elementwise across the vector dimension and sum
    contributions in fixed participant order, so reordering *which bucket*
    goes first never reorders any element's reduction: each bucket's
    result is bitwise identical to reducing it alone
    (tests/test_overlap.py).  Same per-bucket divisibility contract as the
    single-tensor path; collective count is ``2 * depth * len(xs)`` —
    exactly ``len(xs)`` monolithic reductions' worth, no extra phases
    (audited by ``repro.analysis.auditor.audit_overlap_sync``).
    """
    xs = list(xs)
    for st in plan.stages:
        g = list(map(list, st.axis_index_groups))
        xs = [lax.psum_scatter(x, st.axis_name, scatter_dimension=0,
                               axis_index_groups=g, tiled=True) for x in xs]
    for st in reversed(plan.stages):
        g = list(map(list, st.axis_index_groups))
        xs = [lax.all_gather(x, st.axis_name, axis_index_groups=g, axis=0,
                             tiled=True) for x in xs]
    return xs


def dense_allreduce_binary(x: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Degree-2 butterfly (hypercube) allreduce via paired psums."""
    plan = ButterflyPlan(axis_size, (2,) * int(math.log2(axis_size)))
    for l in range(plan.depth):
        g = [list(gr) for gr in plan.axis_index_groups(l)]
        x = lax.psum_scatter(x, axis_name, scatter_dimension=0,
                             axis_index_groups=g, tiled=True)
    for l in reversed(range(plan.depth)):
        g = [list(gr) for gr in plan.axis_index_groups(l)]
        x = lax.all_gather(x, axis_name, axis_index_groups=g, axis=0, tiled=True)
    return x


# ---------------------------------------------------------------------------
# Host-side helpers to run the primitive end to end (tests / examples)
# ---------------------------------------------------------------------------

def run_union_allreduce(mesh: jax.sharding.Mesh, plan: DevicePlan,
                        idx: jax.Array, val: jax.Array,
                        use_kernel: bool = False, merge: str = "sort",
                        dead=None, wire: str = "raw"):
    """Convenience wrapper: shard (idx, val) over the plan's axes and run.

    idx: uint32 [M, C] hashed *sorted* indices per node (SENTINEL padded)
    val: [M, C] or [M, C, W]
    ``merge``: per-layer merge strategy ("sort" | "fused" | "banded"); see
    :func:`sparse_allreduce_union`.
    ``wire``: on-wire payload encoding ("raw" | "delta" | "delta+bf16" |
    "delta+int8ef"); "delta" is bit-identical to "raw", the lossy modes
    trade bounded value error for bytes (see ``kernels.wirecodec``).
    ``dead``: set of dead *physical* node ids for r-way replicated plans
    (``make_device_plan(replication=r)``); the corresponding
    ``contribution_weights`` are applied inside shard_map so each logical
    shard is summed from its first alive replica.  Raises
    ``DeadLogicalNode`` if a whole replica group is dead — with
    ``replication=1`` any non-empty ``dead`` raises (no redundancy).
    Completion probability and overhead: benchmarks/bench_fault_tolerance.py.
    Returns (idx [M, out_cap], val [M, out_cap(,W)], overflow [M]).
    """
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    axis_names = tuple(n for n, _ in plan.axes)
    shape = tuple(s for _, s in plan.axes)
    edges = [jnp.asarray(e) for e in plan.edges_arrays()]
    idx_r = idx.reshape(shape + idx.shape[1:])
    val_r = val.reshape(shape + val.shape[1:])

    weights = None
    if plan.replication > 1 or dead:
        from .replication import contribution_weights
        weights = jnp.asarray(contribution_weights(
            plan.num_nodes, plan.replication, dead)).reshape(shape)

    data_specs = P(*axis_names)
    edge_specs = tuple(P(*axis_names, *([None])) for _ in edges)
    w_specs = (data_specs,) if weights is not None else ()
    w_args = (weights,) if weights is not None else ()

    def body(i, v, *rest):
        if weights is not None:
            w, e = rest[0], rest[1:]
        else:
            w, e = None, rest
        i = i.reshape(i.shape[len(shape):])
        v = v.reshape(v.shape[len(shape):])
        chunk, ovf = sparse_allreduce_union(SparseChunk(idx=i, val=v), plan,
                                            e, use_kernel=use_kernel,
                                            merge=merge, weight=w, wire=wire)
        pad = (1,) * len(shape)
        return (chunk.idx.reshape(pad + chunk.idx.shape),
                chunk.val.reshape(pad + chunk.val.shape),
                ovf.reshape(pad))

    fn = shard_map(body, mesh=mesh,
                   in_specs=(data_specs, data_specs) + w_specs + edge_specs,
                   out_specs=(data_specs, data_specs, data_specs),
                   check_vma=False)
    oi, ov, ovf = fn(idx_r, val_r, *w_args, *edges)
    m = math.prod(shape)
    return (oi.reshape((m,) + oi.shape[len(shape):]),
            ov.reshape((m,) + ov.shape[len(shape):]),
            ovf.reshape((m,)))
