"""Train / serve step builders: shard_map bodies + pjit wrappers.

This is where the paper's primitive becomes a first-class training feature.
Gradient sync over the data axes supports three modes:

  * ``ring``   — stock ``lax.psum`` (XLA ring): every framework's baseline.
  * ``hier``   — the paper's *heterogeneous-degree nested butterfly*, dense:
    reduce-scatter down the degree sequence, all-gather back up
    (core.allreduce.dense_allreduce_hierarchical), degrees tunable.
  * ``sparse`` — the paper's Sparse Allreduce for the input-embedding
    gradient (rows touched by the batch; the paper's mini-batch use case,
    §I-A.1) + hier for everything else.  NOTE: with tied embeddings the
    softmax-head contribution makes the emb grad dense in vocab, so sparse
    mode is exercised on untied variants (DESIGN.md §sync); tied configs
    fall back to hier for that leaf.

FSDP leaves need no explicit sync: the per-period all_gather's transpose IS
the reduce-scatter (sum over data) — they are only rescaled by 1/dp.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core.allreduce import (DevicePlan, dense_allreduce_hierarchical,
                                  dense_allreduce_hierarchical_bucketed,
                                  make_device_plan, sparse_allreduce_union)
from repro.core.sparse_vec import SENTINEL, HashPerm, SparseChunk
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.models.sharding import (full_model_pspec, full_model_spec_tuples,
                                   to_pspec)
from repro.optim.adamw import AdamW, AdamWState

SYNC_PERM = HashPerm.make(1234)


# ---------------------------------------------------------------------------
# Mesh bookkeeping
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    tp_axis: str
    dp_axes: Tuple[str, ...]

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def dp(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    def axis_ctx(self, cfg: ModelConfig) -> T.AxisCtx:
        return T.AxisCtx(tp_axis=self.tp_axis, tp=self.tp,
                         dp_axes=self.dp_axes,
                         fsdp_axes=self.dp_axes if cfg.fsdp else None)


def mesh_ctx(mesh: Mesh) -> MeshCtx:
    names = mesh.axis_names
    dp = tuple(n for n in names if n != "model")
    return MeshCtx(mesh=mesh, tp_axis="model", dp_axes=dp)


def tuned_dp_degrees(mc: MeshCtx, in_capacity: int, out_capacity: int,
                     retune: bool = False) -> Dict[str, Tuple[int, ...]]:
    """Per-axis degree sequences from the *calibrated, cached* autotuner
    (``repro.core.autotune``; TUNING.md).  An EC2-tuned 16x4 is NOT
    optimal on a ~1 us-alpha fabric — see EXPERIMENTS H1 iterations 4-5.
    This is what ``dp_degrees="auto"`` resolves to, for both the
    hierarchical-dense and sparse sync plans.

    Per axis: the fabric is the persisted calibration for this backend
    (``autotune.calibrate_fabric(store=True)``) when one exists, else the
    nominal TPU fabric (``pod`` axis -> DCN, others -> ICI); the degree
    sweep result is read from / written to the persistent plan cache, so
    repeat launches skip the sweep entirely.  ``retune=True`` (CLI
    ``--retune``) bypasses cached reads and overwrites."""
    import jax

    from repro.core import autotune
    from repro.core.netmodel import TPU_DCN, TPU_ICI
    backend = jax.default_backend()
    ndev = len(jax.devices())
    degrees = {}
    for a in mc.dp_axes:
        s = mc.mesh.shape[a]
        nominal = TPU_DCN if a == "pod" else TPU_ICI
        fabric = autotune.calibrated_fabric(
            backend=backend, num_devices=ndev, default=nominal)
        degs, _src = autotune.resolve_degrees(
            s, n0=max(in_capacity, 1), total_range=max(out_capacity, 2) * 4,
            fabric=fabric, serial_nic=False, mesh_sig=((a, s),),
            retune=retune)
        degrees[a] = degs
    return degrees


def default_dp_plan(mc: MeshCtx, in_capacity: int, out_capacity: int,
                    degrees=None, retune: bool = False) -> DevicePlan:
    """Butterfly plan over the data axes (pod stage first — slowest link
    gets the outermost layer, per the paper's degree-ordering argument).

    degrees="auto" runs :func:`tuned_dp_degrees` (calibrated + cached);
    ``None`` keeps one round-robin stage per axis."""
    axes = [(a, mc.mesh.shape[a]) for a in mc.dp_axes]
    if degrees == "auto":
        degrees = tuned_dp_degrees(mc, in_capacity, out_capacity,
                                   retune=retune)
    elif degrees is None:
        degrees = {a: (s,) for a, s in axes}   # round-robin per axis
    return make_device_plan(axes, degrees, in_capacity=in_capacity,
                            out_capacity=out_capacity)


# ---------------------------------------------------------------------------
# Gradient sync (inside shard_map)
# ---------------------------------------------------------------------------

def _hier_allreduce_leaf(g: jax.Array, plan: DevicePlan) -> jax.Array:
    m = plan.num_nodes
    n = g.size
    pad = (-n) % m
    flat = jnp.pad(g.astype(jnp.float32).reshape(-1), (0, pad))
    out = dense_allreduce_hierarchical(flat, plan)
    return out[:n].reshape(g.shape).astype(g.dtype)


# Default bucket byte budget for the overlapped sync schedule: 4 MB sits
# just above the paper's 2-4 MB effective packet floor, so every bucket's
# messages stay bandwidth-bound while still yielding several independent
# buckets on the reduced configs the tests sweep.
DEFAULT_BUCKET_BYTES = 4 << 20

SYNC_OVERLAP_MODES = ("off", "bucketed")


def plan_grad_buckets(sizes: Sequence[int], bucket_bytes: int,
                      bytes_per_elem: int = 4) -> list:
    """Greedy contiguous partition of leaf indices into byte-bounded buckets.

    ``sizes``: element count per gradient leaf, in sync order.  Returns a
    list of index lists such that (a) their concatenation is exactly
    ``range(len(sizes))`` — an order-preserving exact cover, every leaf in
    exactly one bucket; (b) each bucket's total bytes is at most
    ``bucket_bytes`` unless the bucket is a single oversized leaf (a leaf
    larger than the budget gets a bucket of its own rather than being
    split — splitting would change the per-leaf pad-to-num_nodes layout
    and break bitwise parity with the unbucketed path).  Both properties
    hold for every permutation of ``sizes`` (hypothesis-checked in
    tests/test_overlap.py).

    Greedy-contiguous rather than bin-packed on purpose: leaves arrive in
    reverse-backward order, so contiguity is what lets early buckets'
    collectives issue while later grads are still being produced.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    if bytes_per_elem <= 0:
        raise ValueError(
            f"bytes_per_elem must be positive, got {bytes_per_elem}")
    buckets: list = []
    cur: list = []
    cur_bytes = 0
    for i, n in enumerate(sizes):
        if n < 0:
            raise ValueError(f"leaf size must be >= 0, got sizes[{i}]={n}")
        nb = int(n) * bytes_per_elem
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


def _bucketed_hier_leaves(gs: Sequence[jax.Array], plan: DevicePlan,
                          bucket_bytes: int) -> list:
    """Hier-allreduce a list of gradient leaves through the bucketed
    stage-major schedule; returns per-leaf reduced arrays in order.

    Each leaf gets exactly the :func:`_hier_allreduce_leaf` treatment —
    f32 flatten, pad to a ``num_nodes`` multiple, hierarchical allreduce,
    slice, reshape, cast back — except that padded flats are concatenated
    into :func:`plan_grad_buckets` buckets and all buckets traverse the
    butterfly together, stage-major
    (:func:`repro.core.allreduce.dense_allreduce_hierarchical_bucketed`).
    The collectives are elementwise, so the concat + reorder is a pure
    schedule change: every leaf's result is bitwise identical to the
    unbucketed path's (tests/test_overlap.py parity sweep).
    """
    m = plan.num_nodes
    flats = []
    for g in gs:
        pad = (-g.size) % m
        flats.append(jnp.pad(g.astype(jnp.float32).reshape(-1), (0, pad)))
    sizes = [f.size for f in flats]
    buckets = plan_grad_buckets(sizes, bucket_bytes)
    cats = [flats[b[0]] if len(b) == 1
            else jnp.concatenate([flats[i] for i in b])
            for b in buckets]
    reduced = dense_allreduce_hierarchical_bucketed(cats, plan)
    out = [None] * len(gs)
    for b, r in zip(buckets, reduced):
        off = 0
        for i in b:
            out[i] = (r[off:off + gs[i].size]
                      .reshape(gs[i].shape).astype(gs[i].dtype))
            off += sizes[i]
    return out


def sparse_sync_rows(grad: jax.Array, ids: jax.Array, mc: MeshCtx,
                     dplan: DevicePlan, edges: Sequence[jax.Array],
                     merge: str = "sort", wire: str = "raw",
                     ef: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Sparse Allreduce of a row-sparse gradient table over the data axes.

    grad: [V_local, d] this device's vocab-shard gradient (model-sharded).
    ids:  [N] global token ids appearing in the local batch.
    Returns (synced grad, overflow count, new error-feedback carry).
    config+reduce fused — dynamic indices, the paper's mini-batch mode.

    ``wire`` selects the on-wire payload encoding of the union butterfly
    (``repro.kernels.wirecodec``; ``"delta"`` is bit-identical to raw).
    ``ef`` [V_local, d] f32 is this device's error-feedback carry for
    ``wire="delta+int8ef"``: it is added to the rows *sent* this step, and
    the residual of quantizing the sent payload is stored back, so the
    quantization error of each step's contribution is re-injected (not
    lost) on the next step.  The residual uses one per-row int8
    quantization of the sender payload — a bounded proxy for the per-stage
    re-quantization the payload actually undergoes inside the butterfly
    (each stage's merge re-quantizes, so the true end-to-end error is a
    sum of per-stage residuals; carrying the first hop's residual already
    removes the sender-side bias, which dominates).  The returned carry is
    ``None`` when ``ef`` is None.
    """
    v_l, d = grad.shape
    v_start = lax.axis_index(mc.tp_axis) * v_l
    loc = ids.reshape(-1).astype(jnp.int32) - v_start
    mine = (loc >= 0) & (loc < v_l)
    hashed = jnp.where(mine, SYNC_PERM.fwd(ids.reshape(-1).astype(jnp.uint32)),
                       jnp.uint32(SENTINEL))
    hsorted = jnp.sort(hashed)
    n = hsorted.shape[0]
    cap_in = dplan.in_capacity
    valid = hsorted != jnp.uint32(SENTINEL)
    is_head = jnp.concatenate([jnp.ones((1,), bool),
                               hsorted[1:] != hsorted[:-1]]) & valid
    pos = jnp.cumsum(is_head.astype(jnp.int32)) - 1
    uniq = jnp.full((cap_in,), SENTINEL, jnp.uint32)
    uniq = uniq.at[jnp.where(is_head & (pos < cap_in), pos, cap_in)].set(
        hsorted, mode="drop")
    rows = (SYNC_PERM.inv(uniq).astype(jnp.int32) - v_start)
    okr = uniq != jnp.uint32(SENTINEL)
    safe_rows = jnp.clip(rows, 0, v_l - 1)
    vals = grad[safe_rows].astype(jnp.float32) * okr[:, None]
    new_ef = None
    if ef is not None:
        vals = vals + ef[safe_rows].astype(jnp.float32) * okr[:, None]
        from repro.kernels.wirecodec import dequant8_rows, quant8_rows
        q, s = quant8_rows(vals)
        resid = (vals - dequant8_rows(q, s)) * okr[:, None]
        ef_dest = jnp.where(okr, safe_rows, v_l)
        new_ef = (jnp.zeros((v_l + 1, d), jnp.float32)
                  .at[:v_l].set(ef.astype(jnp.float32))
                  .at[ef_dest].set(resid, mode="drop")[:v_l])
    chunk, ovf = sparse_allreduce_union(
        SparseChunk(idx=uniq, val=vals), dplan, edges, merge=merge,
        wire=wire)
    out_rows = (SYNC_PERM.inv(chunk.idx).astype(jnp.int32) - v_start)
    ok = chunk.idx != jnp.uint32(SENTINEL)
    dest = jnp.where(ok, out_rows, v_l)
    synced = jnp.zeros((v_l + 1, d), jnp.float32).at[dest].set(
        chunk.val * ok[:, None], mode="drop")[:-1]
    return synced.astype(grad.dtype), ovf, new_ef


def sync_grads(grads, cfg: ModelConfig, mc: MeshCtx, mode: str,
               hier_plan: Optional[DevicePlan],
               sparse_plan: Optional[DevicePlan],
               sparse_edges, token_ids,
               merge: str = "sort",
               wire: str = "raw",
               ef: Optional[jax.Array] = None,
               repl_weight: Optional[jax.Array] = None,
               dp_logical: Optional[int] = None,
               overlap: str = "off",
               bucket_bytes: int = DEFAULT_BUCKET_BYTES
               ) -> Tuple[Any, jax.Array, Optional[jax.Array]]:
    """Combine per-device grads into the grad of the global mean loss.

    ``repl_weight`` (r-way replicated data parallelism, paper §V): this
    device's scalar ``contribution_weights`` entry.  Replica groups hold
    identical batch shards, so scaling every gradient leaf by the weight
    before the data-axis sum counts each logical shard exactly once — from
    its first alive replica — and the mean divides by ``dp_logical``
    (= dp / r) instead of dp.

    ``wire`` / ``ef`` thread the sparse leaf's on-wire encoding and
    error-feedback carry (:func:`sparse_sync_rows`); the updated carry is
    returned as the third element (``ef`` unchanged when the sparse leaf
    was not synced this step, ``None`` when error feedback is off).

    ``overlap="bucketed"`` reschedules the hierarchical-butterfly leaves:
    instead of one monolithic 2·depth collective chain per leaf, leaves
    are concatenated into ``bucket_bytes``-bounded buckets
    (:func:`plan_grad_buckets`) and all buckets traverse the butterfly
    **stage-major** — every bucket's stage-l exchange issues before any
    stage-l+1 — so early buckets' collectives overlap the remaining
    backward compute and later buckets' sends (ARCHITECTURE.md "Overlap &
    scheduling").  A pure schedule permutation of elementwise collectives:
    results are bitwise identical to ``"off"``, collective totals are
    unchanged, and the sparse / fsdp / psum leaves (including the merge /
    wire / replication machinery) are untouched.
    """
    if overlap not in SYNC_OVERLAP_MODES:
        raise ValueError(
            f"overlap must be one of {SYNC_OVERLAP_MODES}, got {overlap!r}")
    spec = full_model_spec_tuples(cfg, mc.tp)
    dp = float(dp_logical if dp_logical is not None else mc.dp)
    overflow = jnp.zeros((), jnp.int32)
    new_ef = ef
    deferred = []          # (path, weighted grad) awaiting the bucketed pass

    def leaf_sync(path, g, s):
        nonlocal overflow, new_ef
        if cfg.fsdp and any(d == "fsdp" for d in s):
            return g / dp          # transpose already summed over data
        if repl_weight is not None:
            g = g * repl_weight.astype(g.dtype)
        if mode == "sparse" and path == ("emb",) and not cfg.tie_embeddings:
            synced, ovf, nef = sparse_sync_rows(
                g, token_ids, mc, sparse_plan, sparse_edges, merge=merge,
                wire=wire, ef=ef)
            overflow = overflow + ovf
            if nef is not None:
                new_ef = nef
            return synced / dp
        if mode in ("hier", "sparse") and hier_plan is not None and g.size >= mc.dp:
            if overlap == "bucketed":
                deferred.append((path, g))
                return None        # resolved by the bucketed pass below
            return _hier_allreduce_leaf(g, hier_plan) / dp
        out = g
        for a in mc.dp_axes:
            out = lax.psum(out, a)
        return out / dp

    flat = _flatten_with_path(grads)
    sflat = dict(_flatten_with_path(spec))
    synced = [(p, leaf_sync(p, g, sflat[p])) for p, g in flat]
    if deferred:
        reduced = _bucketed_hier_leaves([g for _, g in deferred], hier_plan,
                                        bucket_bytes)
        by_path = {p: r / dp for (p, _), r in zip(deferred, reduced)}
        synced = [(p, by_path[p] if v is None else v) for p, v in synced]
    return _unflatten_from_path(grads, synced), overflow, new_ef


def _flatten_with_path(tree, prefix=()):
    """Dict-structured flatten; non-dict values (arrays OR spec tuples) are
    leaves — param/grad/spec trees here are dicts all the way down."""
    out = []
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.extend(_flatten_with_path(tree[k], prefix + (k,)))
    else:
        out.append((prefix, tree))
    return out


def _unflatten_from_path(like, items):
    d = dict(items)

    def rb(t, prefix=()):
        if isinstance(t, dict):
            return {k: rb(v, prefix + (k,)) for k, v in t.items()}
        return d[prefix]
    return rb(like)


def _sharded_grad_norm(grads, cfg: ModelConfig, mc: MeshCtx) -> jax.Array:
    """Global grad norm with sharding-aware reduction (each distinct param
    element counted exactly once; grads are already data-synced)."""
    spec = full_model_spec_tuples(cfg, mc.tp)
    sflat = dict(_flatten_with_path(spec))
    total = jnp.zeros((), jnp.float32)
    for path, g in _flatten_with_path(grads):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        dims = sflat[path]
        if any(d == "model" for d in dims):
            sq = lax.psum(sq, mc.tp_axis)
        if cfg.fsdp and any(d == "fsdp" for d in dims):
            for a in mc.dp_axes:
                sq = lax.psum(sq, a)
        total = total + sq
    return jnp.sqrt(total)


def init_cache_global(cfg: ModelConfig, mc: MeshCtx, b: int, max_seq: int,
                      seq_sharded: bool = False):
    """Global-shape cache pytree matching cache_pspec (host allocation)."""
    from repro.models import ssm as SSM
    tp = mc.tp
    kvg = cfg.kv_local(tp) * tp
    hd, npd = cfg.hd, cfg.n_periods
    per = {}
    for j, blk in enumerate(cfg.pattern):
        if blk == "attn":
            per[f"b{j}"] = {
                "k": jnp.zeros((npd, b, max_seq, kvg, hd), cfg.dtype),
                "v": jnp.zeros((npd, b, max_seq, kvg, hd), cfg.dtype)}
        elif blk == "mamba":
            dig = SSM.mamba_inner(cfg, tp) * tp
            per[f"b{j}"] = {
                "h": jnp.zeros((npd, b, dig, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((npd, b, cfg.ssm_conv - 1, dig), cfg.dtype)}
        elif blk == "mlstm":
            h, dk, dvl = SSM.mlstm_dims(cfg, tp)
            per[f"b{j}"] = {
                "S": jnp.zeros((npd, b, h, dk, dvl * tp), jnp.float32),
                "N": jnp.zeros((npd, b, h, dk), jnp.float32),
                "m": jnp.zeros((npd, b, h), jnp.float32)}
        elif blk == "slstm":
            dh = cfg.d_model // cfg.n_heads
            per[f"b{j}"] = tuple(
                jnp.zeros((npd, b, cfg.n_heads, dh), jnp.float32)
                for _ in range(4))
    return per


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def _build_sync_plans(cfg: ModelConfig, mc: MeshCtx, mesh: Mesh, sync: str,
                      dp_degrees, sparse_tokens_hint: Optional[int],
                      retune: bool):
    """The gradient-sync plan set for one (cfg, mesh, sync) combination:
    ``(hier_plan, sparse_plan, sparse_edges)`` — shared by
    :func:`make_train_step` and the model-free :func:`make_sync_fn`
    harness so both paths sync through identical routing."""
    sparse_plan = sparse_edges = None
    hier_plan = None
    if sync in ("hier", "sparse"):
        hier_plan = default_dp_plan(mc, 8, 8, dp_degrees, retune=retune)
    if sync == "sparse":
        v_l = T.padded_vocab(cfg, mc.tp) // mc.tp
        # in capacity: unique local rows <= min(tokens/device, vocab shard).
        # Sizing to the actual batch sparsity is what makes the sparse path
        # win (SPerf H1: worst-case capacities moved MORE bytes than ring).
        cin = int(min(v_l, sparse_tokens_hint or (1 << 16)))
        cin = (cin + 7) // 8 * 8
        cout = (min(v_l, cin * mc.dp) + 7) // 8 * 8
        sp_degrees = dp_degrees
        if dp_degrees == "auto":
            sp_degrees = tuned_dp_degrees(mc, cin, cout, retune=retune)
        sparse_plan = make_device_plan(
            [(a, mesh.shape[a]) for a in mc.dp_axes],
            sp_degrees or {a: (mesh.shape[a],) for a in mc.dp_axes},
            in_capacity=cin, out_capacity=cout)
        sparse_edges = [jnp.asarray(e) for e in sparse_plan.edges_arrays()]
    return hier_plan, sparse_plan, sparse_edges


def _check_sync_settings(sync: str, sync_merge: str, sync_wire: str,
                         sync_overlap: str):
    """Shared make_train_step / make_sync_fn validation (fires before any
    mesh work; tests/test_overlap.py, tests/test_wire.py)."""
    from repro.core.allreduce import MERGE_MODES
    from repro.core.topology import check_wire
    if sync_merge not in MERGE_MODES:
        raise ValueError(
            f"sync_merge must be one of {MERGE_MODES}, got {sync_merge!r}")
    check_wire(sync_wire)
    if sync_wire != "raw" and sync != "sparse":
        raise ValueError(
            f"sync_wire={sync_wire!r} only applies to the sparse sync path "
            f"(got sync={sync!r}); ring/hier sync is dense and unencoded")
    if sync_overlap not in SYNC_OVERLAP_MODES:
        raise ValueError(f"sync_overlap must be one of {SYNC_OVERLAP_MODES}, "
                         f"got {sync_overlap!r}")
    if sync_overlap == "bucketed" and sync not in ("hier", "sparse"):
        raise ValueError(
            f"sync_overlap='bucketed' requires sync in ('hier', 'sparse') "
            f"(got sync={sync!r}): ring sync is a single psum per leaf with "
            f"no butterfly stages to interleave")


def make_sync_fn(cfg: ModelConfig, mesh: Mesh, *, sync: str = "hier",
                 dp_degrees=None,
                 sync_merge: str = "sort",
                 sync_wire: str = "raw",
                 replication: int = 1,
                 dead: Optional[set] = None,
                 sync_overlap: str = "off",
                 sync_bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 sparse_tokens_hint: Optional[int] = None,
                 retune: bool = False,
                 salt_shards: bool = True):
    """The gradient-sync stage of :func:`make_train_step` as a standalone
    jitted callable — no model forward/backward attached.

    Returns ``(fn, pspec)``: ``fn(grads, token_ids) -> (synced_grads,
    overflow)`` where ``grads`` is a global (fully addressable) param-tree
    of gradients laid out per ``full_model_pspec`` and ``token_ids`` is
    the ``[B, S]`` token batch the sparse leaf's row union is built from
    (dp-sharded like the train batch; ignored unless ``sync="sparse"``).

    This is the bit-exactness harness entry (tests/test_overlap.py): the
    parity sweep runs the *same* plan / merge / wire / replication /
    overlap machinery as the full train step — through the shared
    :func:`_build_sync_plans` and :func:`sync_grads` — while dispatching
    only the sync collectives, so a 36-combination 16-device sweep stays
    tractable.  Error feedback is not threaded (``wire="delta+int8ef"``
    syncs with a zero carry); use the full step for EF semantics.

    ``salt_shards`` (default on — this is a harness): non-fsdp gradient
    leaves arrive data-replicated under ``full_model_pspec``, which would
    let contribution-routing bugs cancel symmetrically; the body therefore
    scales each *logical* data shard's gradients by a distinct power-of-two
    factor before syncing.  Dyadic factors keep dyadic-lattice test values
    exactly representable, and salting by logical (not physical) shard
    keeps r-way replicas identical, so replicated results stay invariant
    to any survivable ``dead`` set.
    """
    _check_sync_settings(sync, sync_merge, sync_wire, sync_overlap)
    mc = mesh_ctx(mesh)
    repl_weights = None
    dp_logical = mc.dp
    if replication > 1 or dead:
        from repro.core.replication import contribution_weights
        if mc.dp % replication:
            raise ValueError(f"dp={mc.dp} not divisible by r={replication}")
        repl_weights = contribution_weights(mc.dp, replication, dead)
        dp_logical = mc.dp // replication
    hier_plan, sparse_plan, sparse_edges = _build_sync_plans(
        cfg, mc, mesh, sync, dp_degrees, sparse_tokens_hint, retune)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dspec = P(mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0])
    edge_specs = tuple(P(*mc.dp_axes, None) for _ in (sparse_edges or ()))

    def body(grads, tokens, *edges):
        flat = jnp.zeros((), jnp.int32)
        for a in mc.dp_axes:
            flat = flat * mesh.shape[a] + lax.axis_index(a)
        if salt_shards:
            salt = jnp.exp2(-((flat % dp_logical) % 4).astype(jnp.float32))
            grads = jax.tree.map(lambda g: g * salt.astype(g.dtype), grads)
        repl_w = None
        if repl_weights is not None:
            repl_w = jnp.asarray(repl_weights)[flat]
        synced, overflow, _ = sync_grads(
            grads, cfg, mc, sync, hier_plan, sparse_plan, edges, tokens,
            merge=sync_merge, wire=sync_wire, ef=None, repl_weight=repl_w,
            dp_logical=dp_logical, overlap=sync_overlap,
            bucket_bytes=sync_bucket_bytes)
        return synced, overflow

    sm = shard_map(body, mesh=mesh,
                   in_specs=(pspec, dspec) + edge_specs,
                   out_specs=(pspec, P()), check_vma=False)

    def fn(grads, token_ids):
        return sm(grads, token_ids, *(sparse_edges or ()))

    return fn, pspec


def train_fingerprint(cfg: ModelConfig, **settings) -> str:
    """Digest of everything that must match for a checkpoint to resume
    *exactly*: the model config plus caller-provided run settings (batch,
    seq, seed, sync mode, ...).  Stored in checkpoint meta by
    ``repro.launch.soak`` and compared on resume — a mismatch means the
    resumed trajectory could silently diverge from the original run, so
    the harness refuses it rather than producing not-quite-identical
    steps."""
    import hashlib
    import json
    payload = {"cfg": dataclasses.asdict(cfg),
               "settings": {k: settings[k] for k in sorted(settings)}}
    return hashlib.sha1(
        json.dumps(payload, sort_keys=True, default=str).encode()
    ).hexdigest()[:16]


def make_train_step(cfg: ModelConfig, mesh: Mesh, *, sync: str = "ring",
                    opt: Optional[AdamW] = None,
                    dp_degrees=None,
                    aux_weight: float = 0.01, donate: bool = True,
                    microbatch: int = 1,
                    sparse_tokens_hint: Optional[int] = None,
                    sync_merge: str = "sort",
                    sync_wire: str = "raw",
                    replication: int = 1,
                    dead: Optional[set] = None,
                    retune: bool = False,
                    sync_overlap: str = "off",
                    sync_bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """Returns (step_fn, specs) — step_fn is jit-compiled with shardings.

    step_fn(params, opt_state, batch) -> (params, opt_state, metrics)
    batch dict: tokens, labels [+ img_embeds / enc_frames].

    ``dp_degrees``: per-data-axis butterfly degree dict for the hier /
    sparse sync plans, the string ``"auto"`` to resolve per axis through
    the calibrated, plan-cached autotuner (:func:`tuned_dp_degrees`;
    ``retune=True`` forces a fresh sweep past the cache), or ``None`` for
    one round-robin stage per axis.

    ``sync_merge`` ("sort" | "fused" | "banded") selects the
    per-butterfly-layer merge of the sparse embedding-grad allreduce
    (core.allreduce docstring; "banded" is the band-limited Pallas
    pipeline with near-linear per-layer tile work).

    ``sync_wire`` ("raw" | "delta" | "delta+bf16" | "delta+int8ef")
    selects the on-wire payload encoding of that same sparse allreduce
    (``repro.kernels.wirecodec``; sparse sync only — other modes raise).
    ``"delta"`` bit-packs indices and is bit-identical to raw;
    ``"delta+int8ef"`` additionally quantizes values to per-row int8 with
    an *error-feedback carry*: the returned step fn transparently wraps
    the optimizer state as ``{"adamw": opt_state, "ef": carry}`` on first
    call (pass a bare AdamWState the first step; thereafter pass the dict
    the step returned) and the per-device quantization residual is
    re-injected into the next step's sent gradient
    (:func:`sparse_sync_rows`).

    microbatch > 1 splits the per-device batch into that many accumulation
    steps (lax.scan) — bounds activation / MoE-dispatch memory; gradients
    are synced once per step, after accumulation (so the paper's allreduce
    sees the full-batch sparsity union, as in its mini-batch use case).

    ``replication=r`` (paper §V fault tolerance) treats the flattened data
    axes (size dp) as dp/r logical batch shards hosted r-way redundantly
    per ``repro.core.replication.replica_groups`` — the launcher feeds each
    replica group the same batch shard (train.py tiles the logical batch r
    times) and gradient sync takes every logical contribution from its
    first alive replica via ``contribution_weights``, so step results are
    unchanged by any ``dead`` set that leaves each group one alive member.
    Raises ``DeadLogicalNode`` otherwise (with r=1, on any failure).

    ``sync_overlap="bucketed"`` (hier / sparse sync only) reschedules the
    dense butterfly leaves into ``sync_bucket_bytes``-bounded buckets
    issued stage-major, so gradient sync interleaves with the surrounding
    compute instead of forming one monolithic collective chain — bitwise
    identical results, same collective totals (see :func:`sync_grads`;
    ARCHITECTURE.md "Overlap & scheduling"; CLI ``--sync-overlap``).
    """
    _check_sync_settings(sync, sync_merge, sync_wire, sync_overlap)
    mc = mesh_ctx(mesh)
    ax = mc.axis_ctx(cfg)
    opt = opt or AdamW()
    repl_weights = None
    dp_logical = mc.dp
    if replication > 1 or dead:
        from repro.core.replication import contribution_weights
        if cfg.fsdp and replication > 1:
            raise ValueError(
                "replication>1 is unsupported with fsdp: the per-period "
                "all_gather transpose sums FSDP leaf grads over data before "
                "contribution weights could mask replicas")
        if mc.dp % replication:
            raise ValueError(f"dp={mc.dp} not divisible by r={replication}")
        # raises DeadLogicalNode if a whole replica group is dead
        repl_weights = contribution_weights(mc.dp, replication, dead)
        dp_logical = mc.dp // replication
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dspec = P(mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0])

    hier_plan, sparse_plan, sparse_edges = _build_sync_plans(
        cfg, mc, mesh, sync, dp_degrees, sparse_tokens_hint, retune)

    # int8ef error-feedback carry: per-device sender state over the vocab
    # shard, [dp, V_pad, d] globally so every (data, model) device owns one
    # [V_local, d] slab (leading dp dim = one carry per sender).
    ef_shape = None
    ef_spec = None
    if sync == "sparse" and sync_wire == "delta+int8ef":
        ef_shape = (mc.dp, T.padded_vocab(cfg, mc.tp), cfg.d_model)
        ef_spec = P(mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0],
                    "model", None)

    opt_pspec = AdamWState(step=P(), m=pspec, v=pspec)
    if ef_spec is not None:
        opt_pspec = {"adamw": opt_pspec, "ef": ef_spec}
    batch_specs = {"tokens": dspec, "labels": dspec}
    if cfg.img_tokens:
        batch_specs["img_embeds"] = dspec
    if cfg.enc_layers:
        batch_specs["enc_frames"] = dspec

    edge_specs = tuple(P(*mc.dp_axes, None) for _ in (sparse_edges or ()))

    def body(params, opt_state, batch, *edges):
        tokens, labels = batch["tokens"], batch["labels"]
        ef = None
        if ef_spec is not None:
            ef = opt_state["ef"][0]          # local slab [V_local, d]
            opt_state = opt_state["adamw"]

        def loss_fn(p, mb):
            loss, aux = T.forward_loss(
                p, mb["tokens"], mb["labels"], cfg, ax,
                extra_embeds=mb.get("img_embeds"),
                enc_frames=mb.get("enc_frames"))
            return loss + aux_weight * aux, (loss, aux)

        if microbatch == 1:
            grads, (loss, aux) = jax.grad(loss_fn, has_aux=True)(
                params, batch)
        else:
            mb_batch = {k: v.reshape((microbatch, v.shape[0] // microbatch)
                                     + v.shape[1:])
                        for k, v in batch.items()}

            def acc_step(carry, mb):
                g_acc, l_acc, a_acc = carry
                g, (l, a) = jax.grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(
                    lambda x, y: x + y.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l, a_acc + a), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss, aux), _ = lax.scan(
                acc_step, (g0, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), mb_batch)
            grads = jax.tree.map(lambda g: g / microbatch, grads)
            loss, aux = loss / microbatch, aux / microbatch
        repl_w = None
        if repl_weights is not None:
            # flat data-parallel index, row-major over the dp axes (the
            # same order batch rows shard), selects this device's weight
            flat = jnp.zeros((), jnp.int32)
            for a in mc.dp_axes:
                flat = flat * mesh.shape[a] + lax.axis_index(a)
            repl_w = jnp.asarray(repl_weights)[flat]
        grads, overflow, new_ef = sync_grads(
            grads, cfg, mc, sync, hier_plan, sparse_plan, edges, tokens,
            merge=sync_merge, wire=sync_wire, ef=ef, repl_weight=repl_w,
            dp_logical=dp_logical, overlap=sync_overlap,
            bucket_bytes=sync_bucket_bytes)
        gnorm = _sharded_grad_norm(grads, cfg, mc)
        new_params, new_opt, _ = opt.update(grads, opt_state, params,
                                            gnorm=gnorm)
        if ef_spec is not None:
            new_opt = {"adamw": new_opt, "ef": new_ef[None]}
        metrics = {"loss": lax.pmean(loss, mc.dp_axes),
                   "aux": lax.pmean(aux, mc.dp_axes), "gnorm": gnorm,
                   "sync_overflow": overflow}
        return new_params, new_opt, metrics

    sm = shard_map(
        body, mesh=mesh,
        in_specs=(pspec, opt_pspec, batch_specs) + edge_specs,
        out_specs=(pspec, opt_pspec,
                   {"loss": P(), "aux": P(), "gnorm": P(),
                    "sync_overflow": P()}),
        check_vma=False)

    def step(params, opt_state, batch):
        args = (params, opt_state, batch) + tuple(sparse_edges or ())
        return sm(*args)

    mspec = {"loss": P(), "aux": P(), "gnorm": P(), "sync_overflow": P()}
    jit_kw = dict(
        in_shardings=(_ns(mesh, pspec), _ns(mesh, opt_pspec),
                      _ns(mesh, batch_specs)),
        out_shardings=(_ns(mesh, pspec), _ns(mesh, opt_pspec),
                       _ns(mesh, mspec)))
    if donate:
        jit_kw["donate_argnums"] = (0, 1)
    jitted = jax.jit(step, **jit_kw)
    specs = dict(params=pspec, opt=opt_pspec, batch=batch_specs)
    if ef_shape is None:
        return jitted, specs

    def step_with_ef(params, opt_state, batch):
        # Transparent first-call wrap: a bare optimizer state gets a zero
        # error-feedback carry attached; thereafter callers pass the
        # {"adamw": ..., "ef": ...} dict the step returned.
        if not (isinstance(opt_state, dict) and "ef" in opt_state):
            opt_state = {"adamw": opt_state,
                         "ef": jnp.zeros(ef_shape, jnp.float32)}
        return jitted(params, opt_state, batch)

    return step_with_ef, specs


def _ns(mesh: Mesh, spec_tree):
    from jax.sharding import NamedSharding
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def init_params_sharded(cfg: ModelConfig, mesh: Mesh, seed: int = 0):
    """``transformer.init_params`` created in place on ``mesh``: the init
    runs under ``jit`` with the steps' parameter shardings, so each device
    computes its own shard and nothing is staged on device 0 first."""
    mc = mesh_ctx(mesh)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    return jax.jit(lambda: T.init_params(cfg, mc.tp, seed=seed),
                   out_shardings=_ns(mesh, pspec))()


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def cache_pspec(cfg: ModelConfig, mc: MeshCtx, seq_sharded: bool):
    """PartitionSpec tree mirroring transformer.init_cache."""
    dp = mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0]
    bspec = None if seq_sharded else dp
    sspec = "data" if seq_sharded else None
    per = {}
    for j, blk in enumerate(cfg.pattern):
        if blk == "attn":
            kv = "model" if cfg.n_kv >= mc.tp else "model"
            per[f"b{j}"] = {"k": P(None, bspec, sspec, "model", None),
                            "v": P(None, bspec, sspec, "model", None)}
        elif blk == "mamba":
            per[f"b{j}"] = {"h": P(None, bspec, "model", None),
                            "conv": P(None, bspec, None, "model")}
        elif blk == "mlstm":
            per[f"b{j}"] = {"S": P(None, bspec, None, None, "model"),
                            "N": P(None, bspec, None, None),
                            "m": P(None, bspec, None)}
        elif blk == "slstm":
            per[f"b{j}"] = tuple(P(None, bspec, None, None) for _ in range(4))
    return per


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, max_seq: int):
    """serve prefill: (params, batch) -> (local logits, cache)."""
    mc = mesh_ctx(mesh)
    ax = mc.axis_ctx(cfg)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dp = mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0]
    dspec = P(dp)
    batch_specs = {"tokens": dspec}
    if cfg.img_tokens:
        batch_specs["img_embeds"] = dspec
    if cfg.enc_layers:
        batch_specs["enc_frames"] = dspec

    def body(params, batch):
        return T.forward_prefill(params, batch["tokens"], cfg, ax, max_seq,
                                 enc_frames=batch.get("enc_frames"),
                                 extra_embeds=batch.get("img_embeds"))

    cspec = cache_pspec(cfg, mc, False)
    sm = shard_map(body, mesh=mesh, in_specs=(pspec, batch_specs),
                   out_specs=(P(dp, "model"), cspec),
                   check_vma=False)
    jit_kw = dict(in_shardings=(_ns(mesh, pspec), _ns(mesh, batch_specs)),
                  out_shardings=(_ns(mesh, P(dp, "model")), _ns(mesh, cspec)))
    return jax.jit(sm, **jit_kw), dict(params=pspec, batch=batch_specs)


def make_decode_step(cfg: ModelConfig, mesh: Mesh, *, seq_sharded: bool = False,
                     seq_shards: int = 1, serve2d: bool = False):
    """serve decode: (params, token, pos, cache[, cross_cache]) ->
    (local logits, new cache)."""
    mc = mesh_ctx(mesh)
    ax = mc.axis_ctx(cfg)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dp = mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0]
    bspec = P(None) if seq_sharded else P(dp)
    cspec = cache_pspec(cfg, mc, seq_sharded)
    lspec = P(None, "model") if seq_sharded else P(dp, "model")

    cross_spec = None
    if cfg.enc_layers:
        cross_spec = (P(None, dp, None, "model", None),
                      P(None, dp, None, "model", None))

    mesh_sizes = dict(mesh.shape)

    def body(params, token, pos, cache, *cross):
        cc = cross[0] if cross else None
        return T.forward_decode(
            params, token, pos, cache, cfg, ax,
            seq_axis="data" if seq_sharded else None,
            seq_shards=seq_shards, cross_cache=cc,
            serve2d=serve2d, mesh_sizes=mesh_sizes)

    in_specs = (pspec, bspec, bspec, cspec)
    if cfg.enc_layers:
        in_specs = in_specs + (cross_spec,)
    sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=(lspec, cspec), check_vma=False)
    jit_kw = dict(in_shardings=tuple(_ns(mesh, s) for s in in_specs),
                  out_shardings=(_ns(mesh, lspec), _ns(mesh, cspec)))
    return jax.jit(sm, **jit_kw), dict(params=pspec, cache=cspec)


def _greedy_ids(logits: jax.Array, vocab: int) -> jax.Array:
    """On-device greedy sampling over gathered ``[B, V_pad]`` logits.

    The padding columns (``vocab <= j < V_pad``) are exactly zero under
    tied embeddings (zero-initialized pad rows), which can beat
    all-negative real logits — so they are masked to ``-inf`` before the
    argmax, not sliced on host.  Returns int32 ``[B]`` token ids: the
    only thing the serving loop ever transfers
    (``repro.analysis.auditor.audit_serve_decode`` pins this)."""
    v_pad = logits.shape[-1]
    masked = jnp.where(jnp.arange(v_pad) < vocab,
                       logits.astype(jnp.float32), -jnp.inf)
    return jnp.argmax(masked, axis=-1).astype(jnp.int32)


def make_prefill_greedy_step(cfg: ModelConfig, mesh: Mesh, max_seq: int):
    """Fused prefill + on-device greedy: (params, batch) -> (ids, cache).

    ``ids`` is int32 ``[B]`` — the greedy next token after the prompt.
    Same trace as :func:`make_prefill_step` with :func:`_greedy_ids`
    fused at the jit level, so the vocab-sized logits never leave the
    device (the serving tier's fix for the per-step host logits copy)."""
    mc = mesh_ctx(mesh)
    ax = mc.axis_ctx(cfg)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dp = mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0]
    dspec = P(dp)
    batch_specs = {"tokens": dspec}
    if cfg.img_tokens:
        batch_specs["img_embeds"] = dspec
    if cfg.enc_layers:
        batch_specs["enc_frames"] = dspec

    def body(params, batch):
        return T.forward_prefill(params, batch["tokens"], cfg, ax, max_seq,
                                 enc_frames=batch.get("enc_frames"),
                                 extra_embeds=batch.get("img_embeds"))

    cspec = cache_pspec(cfg, mc, False)
    sm = shard_map(body, mesh=mesh, in_specs=(pspec, batch_specs),
                   out_specs=(P(dp, "model"), cspec), check_vma=False)

    def fn(params, batch):
        logits, cache = sm(params, batch)
        return _greedy_ids(logits, cfg.vocab), cache

    jit_kw = dict(in_shardings=(_ns(mesh, pspec), _ns(mesh, batch_specs)),
                  out_shardings=(_ns(mesh, P(dp)), _ns(mesh, cspec)))
    return jax.jit(fn, **jit_kw), dict(params=pspec, batch=batch_specs)


def make_decode_greedy_step(cfg: ModelConfig, mesh: Mesh, *,
                            seq_sharded: bool = False, seq_shards: int = 1,
                            serve2d: bool = False):
    """Fused decode + on-device greedy: (params, token, pos, cache
    [, cross_cache]) -> (ids, new cache).

    The continuous-batching scheduler's step function
    (``repro.serve.scheduler``): one jitted program per slot-count
    bucket, int32 ``[B]`` ids out — no vocab-sized aval in the output
    signature (audited by ``audit_serve_decode``)."""
    mc = mesh_ctx(mesh)
    ax = mc.axis_ctx(cfg)
    pspec = full_model_pspec(cfg, mc.tp, mc.dp_axes)
    dp = mc.dp_axes if len(mc.dp_axes) > 1 else mc.dp_axes[0]
    bspec = P(None) if seq_sharded else P(dp)
    cspec = cache_pspec(cfg, mc, seq_sharded)
    lspec = P(None, "model") if seq_sharded else P(dp, "model")

    cross_spec = None
    if cfg.enc_layers:
        cross_spec = (P(None, dp, None, "model", None),
                      P(None, dp, None, "model", None))

    mesh_sizes = dict(mesh.shape)

    def body(params, token, pos, cache, *cross):
        cc = cross[0] if cross else None
        return T.forward_decode(
            params, token, pos, cache, cfg, ax,
            seq_axis="data" if seq_sharded else None,
            seq_shards=seq_shards, cross_cache=cc,
            serve2d=serve2d, mesh_sizes=mesh_sizes)

    in_specs = (pspec, bspec, bspec, cspec)
    if cfg.enc_layers:
        in_specs = in_specs + (cross_spec,)
    sm = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=(lspec, cspec), check_vma=False)

    def fn(params, token, pos, cache, *cross):
        logits, new_cache = sm(params, token, pos, cache, *cross)
        return _greedy_ids(logits, cfg.vocab), new_cache

    jit_kw = dict(in_shardings=tuple(_ns(mesh, s) for s in in_specs),
                  out_shardings=(_ns(mesh, bspec), _ns(mesh, cspec)))
    return jax.jit(fn, **jit_kw), dict(params=pspec, cache=cspec)
