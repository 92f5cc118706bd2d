#!/usr/bin/env python3
"""Smoke run on the TPU: each main path of the system once, through the
entry point a user calls, at published widths, checked against a plain
reference.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the cross-chip paths on four chips

One chip runs four phases in order:

* ``train``  — ``repro.launch.train`` on qwen1.5-0.5b (24 layers, d_model
  1024, vocab 151,936, untied) with sparse gradient sync, batch 8 x seq
  512, 3 steps: every loss finite, the first within 0.5 of ln(vocab) + 1/2;
  then one step of the same train step on its first batch, whose loss on
  that batch must fall (a wrong gradient or update would not).
* ``serve``  — the continuous-batching tier (``repro.serve``) on the same
  model, 4 Zipf-prompt requests x 16 tokens: token for token equal to the
  one-request-at-a-time oracle (``run_sequential_oracle``).
* ``graph``  — ``pagerank(backend="device")`` through the graph engine on
  one partition of the largest Chung-Lu graph (alpha 2.2, 16 edges per
  vertex) whose ELL tables fit in 4 GiB: within rtol 1e-4 of the dense
  reference.
* ``merge``  — ``kernels.ops.merge_sorted_runs`` fused and banded, compiled,
  at C=65,536 W=1 and C=4,096 W=1024: bit-identical to the sort path on
  values with 21 significant bits, which bf16 rounding on the MXU would
  change.

``--four-chips`` runs only the paths that exist across chips: the sparse
allreduce (planned ``config``/``reduce`` and the union path with each merge)
at degrees (4,) and (2,2) against the simulator and a sort oracle, the graph
engine with M=4 against the dense reference, and data-parallel training
over 4 chips with sparse sync against ring sync.

Each phase prints its wall time, its compile time and the numbers it
compared.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase makes it ``"ok": false`` and the exit code 1.  Without a
TPU, or outside a checkout of the repo, the script exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "qwen1.5-0.5b"             # the model of the train and serve phases
GRAPH_ELL_BYTES = 4 * 2 ** 30     # ELL table budget of the one-chip graph

# JAX events whose durations count as compile time: the XLA backend
# compile, or the persistent-cache read that replaces it (tracing and
# lowering events nest, so they are left out rather than double-counted)
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class _CompileClock:
    """Seconds JAX spent compiling since :meth:`reset`."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def reset(self) -> None:
        self.seconds = 0.0


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def _launch_train(batch: int, seq: int, steps: int, data_axis: int,
                  sync: str) -> dict:
    """Steps of ``repro.launch.train``; every loss finite and the first one
    where a random init puts it.

    At init the final RMSNorm leaves unit-RMS activations and the head's
    weights are N(0, 1/d_model), so each logit is ~N(0, 1) and the
    expected cross-entropy is ln(vocab) + 1/2 (E[logsumexp] of V iid
    N(0, 1) logits is ln V + sigma^2/2)."""
    from repro.configs import get_config
    from repro.launch.train import train
    losses = train(["--arch", ARCH, "--untied", "--sync", sync,
                    "--steps", str(steps), "--batch", str(batch),
                    "--seq", str(seq), "--data-axis", str(data_axis),
                    "--dp-degrees", "rr" if data_axis == 1 else str(data_axis)])
    expected = math.log(get_config(ARCH).vocab) + 0.5
    ok = (len(losses) == steps and all(math.isfinite(x) for x in losses)
          and abs(losses[0] - expected) < 0.5)
    return {"ok": ok, "losses": losses, "init_loss_expected": expected}


def _first_update_losses(batch: int, seq: int, seed: int = 0) -> tuple:
    """Loss of the launcher's train step (same settings, same first batch)
    on that batch before and after one update.  Later steps on the same
    batch are left out: at the launcher's lr with no warmup AdamW's sign
    steps overshoot at this width, so their losses say nothing about the
    update's correctness."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import batch_stream, init_sharded_state
    from repro.train.step import _ns, make_train_step
    cfg = dataclasses.replace(get_config(ARCH), tie_embeddings=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    step, specs = make_train_step(cfg, mesh, sync="sparse",
                                  sparse_tokens_hint=batch * seq)
    params, opt = init_sharded_state(cfg, mesh, seed=seed)
    shard = _ns(mesh, specs["batch"])
    first = next(batch_stream(cfg, batch, seq, seed=seed))
    b = {k: jax.device_put(v, shard[k]) for k, v in first.items()}
    params, opt, before = step(params, opt, b)
    _, _, after = step(params, opt, b)
    return float(before["loss"]), float(after["loss"])


def phase_train(batch: int = 8, seq: int = 512, steps: int = 3) -> dict:
    """The launcher on one chip, then the update check: one step on the
    first batch must lower that batch's loss."""
    out = _launch_train(batch, seq, steps, 1, "sparse")
    before, after = _first_update_losses(batch, seq)
    out.update(ok=out["ok"] and after < before,
               first_update_losses=[before, after])
    return out


def phase_serve(requests: int = 4, prompt_len: int = 64, gen: int = 16,
                seed: int = 0) -> dict:
    """Continuous-batching decode; every request's tokens equal the
    sequential oracle's."""
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.serve import (ContinuousBatchingScheduler, DecodeService,
                             zipf_request_stream)
    from repro.serve.service import run_sequential_oracle
    from repro.train.step import init_params_sharded
    cfg = get_config(ARCH)
    mesh = make_mesh((1, 1), ("data", "model"))
    params = init_params_sharded(cfg, mesh, seed=seed)
    reqs = zipf_request_stream(requests, cfg.vocab, prompt_lens=(prompt_len,),
                               max_new=(gen, gen), seed=seed)
    sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=requests,
                                        max_seq=prompt_len + gen + 1)
    report = DecodeService(sched).run(reqs)
    batched = {r.rid: list(r.tokens) for r in report.completed}
    sched.reset()
    oracle = run_sequential_oracle(sched, reqs)
    equal = [batched.get(r.rid) == oracle[i] for i, r in enumerate(reqs)]
    ok = (len(report.completed) == requests and all(equal)
          and all(len(t) == gen for t in oracle))
    return {"ok": ok, "requests": requests, "tokens_each": gen,
            "equal_to_oracle": sum(equal), "first_tokens": oracle[0][:8]}


def _chung_lu(n: int, alpha: float, edge_factor: int, seed: int):
    from repro.data.pipeline import powerlaw_graph
    return powerlaw_graph(n, edge_factor * n, alpha=alpha, seed=seed)


def _ell_shape(edges, n: int):
    """(R, K) of a single partition's plain ELL tables: rows written, max
    in-degree — what ``build_ell`` allocates (the engine's degree-sliced
    tables, ``ell_extras``, hold far fewer slots on a hub-heavy graph)."""
    import numpy as np
    dst = edges[:, 1]
    rows = len(np.unique(dst))
    k = int(np.bincount(dst, minlength=n).max()) if len(dst) else 1
    return rows, max(k, 1)


def largest_graph(max_ell_bytes: float, alpha: float = 2.2,
                  edge_factor: int = 16, seed: int = 0):
    """Largest Chung-Lu graph (a power of two of vertices, then bisected)
    whose one-partition ELL tables (int32 cols + f32 weights) fit in
    ``max_ell_bytes``.  Returns ``(edges, n, rows, k)``."""
    def fits(n):
        edges = _chung_lu(n, alpha, edge_factor, seed)
        r, k = _ell_shape(edges, n)
        return r * k * 8 <= max_ell_bytes, (edges, n, r, k)

    lo = 256
    ok, best = fits(lo)
    if not ok:
        raise ValueError(f"no graph fits in {max_ell_bytes} ELL bytes")
    hi = lo * 2
    while True:
        ok, got = fits(hi)
        if not ok:
            break
        lo, best, hi = hi, got, hi * 2
    while hi - lo > max(lo // 64, 1):
        mid = (lo + hi) // 2
        ok, got = fits(mid)
        if ok:
            lo, best = mid, got
        else:
            hi = mid
    return best


def phase_graph(m: int = 1, degrees=(), iters: int = 10, seed: int = 0,
                edges=None, n=None) -> dict:
    """PageRank through the device graph engine vs the dense reference;
    without ``edges``, on the largest graph whose ELL tables fit in
    :data:`GRAPH_ELL_BYTES`."""
    import numpy as np
    from repro.graph.pagerank import pagerank, pagerank_dense_reference
    if edges is None:
        edges, n, rows, k = largest_graph(GRAPH_ELL_BYTES, seed=seed)
    else:
        rows, k = _ell_shape(edges, n)
    t0 = time.time()
    ref = pagerank_dense_reference(edges, n, iters=iters)
    t_ref = time.time() - t0
    got, stats = pagerank(edges, n, m=m, degrees=degrees, iters=iters,
                          backend="device", seed=seed)
    err = np.abs(got - ref)
    rel = float(np.max(err / np.abs(ref)))
    ok = bool(np.allclose(got, ref, rtol=1e-4, atol=0.0)) \
        and stats["engine"]["dispatches"] == 1
    out = {"ok": ok, "vertices": n, "edges": int(len(edges)), "m": m,
           "iters": iters, "max_rel_err": rel, "ref_s": round(t_ref, 3)}
    if m == 1:
        out.update(ell_rows=rows, ell_k=k, ell_bytes=rows * k * 8,
                   padding_factor=rows * k / len(edges))
    return out


def _lattice(rng, shape):
    """Values k / 2^20 with |k| <= 2^20: up to 21 significant bits, so
    rounding them to bf16 (8 bits) changes almost every one, while any
    sum of up to 8 of them is exact in f32 and so the same in every
    summation order."""
    import numpy as np
    return (rng.randint(-2 ** 20, 2 ** 20 + 1, shape) / 2.0 ** 20) \
        .astype(np.float32)


def _union_runs(k: int, cap: int, width: int, seed: int):
    """``k`` sorted, SENTINEL-padded runs of unique hashed Zipf indices —
    what a device holds after a butterfly ``all_to_all``."""
    import numpy as np
    from repro.core.sparse_vec import HashPerm
    from repro.data.pipeline import zipf_tokens
    rng = np.random.RandomState(seed)
    perm = HashPerm.make(seed + 1)
    idx = np.full((k, cap), 0xFFFFFFFF, np.uint32)
    val = np.zeros((k, cap) + ((width,) if width > 1 else ()), np.float32)
    for r in range(k):
        raw = zipf_tokens(rng, (4 * cap,), 1 << 22, alpha=1.2)
        h = np.unique(perm.fwd_np(raw.astype(np.uint32)))[:cap]
        idx[r, : len(h)] = h
        val[r, : len(h)] = _lattice(rng, (len(h),) + val.shape[2:])
    return idx, val


def phase_merge(cases=((65536, 1), (4096, 1024)), k: int = 4,
                seed: int = 0) -> dict:
    """Fused and banded merge pipelines vs the sort path, bit for bit; on
    the chip the program must hold the compiled Mosaic kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sparse_vec as sv
    from repro.kernels import ops
    on_tpu = jax.default_backend() == "tpu"
    out = {"ok": True, "interpret": ops.interpret_mode()}
    merge = jax.jit(ops.merge_sorted_runs, static_argnums=(2,),
                    static_argnames=("mode",))
    for c, w in cases:
        idx, val = _union_runs(k, c // k, w, seed + c + w)
        idx, val = jnp.asarray(idx), jnp.asarray(val)
        cat = jax.jit(sv.concat_sorted_groups)(idx, val)
        want = jax.jit(sv.segment_compact, static_argnums=1)(cat, c)
        for mode in ops.MERGE_KERNEL_MODES:
            got, ovf = merge(idx, val, c, mode=mode)
            same = (np.array_equal(np.asarray(got.idx), np.asarray(want.idx))
                    and np.array_equal(np.asarray(got.val),
                                       np.asarray(want.val))
                    and int(ovf) == 0)
            kernels = merge.lower(idx, val, c, mode=mode).compile() \
                .as_text().count("tpu_custom_call")
            out[f"C{c}_W{w}_{mode}"] = {
                "bit_identical": same, "mosaic_kernels": kernels,
                "unique": int(np.sum(np.asarray(got.idx) != 0xFFFFFFFF))}
            out["ok"] = out["ok"] and same and (kernels > 0 or not on_tpu)
    return out


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------

def _power_law_sets(m: int, n: int, edge_factor: int, seed: int):
    """The paper's index sets: per node, the unique columns read (in) and
    rows written (out) by its share of a random edge partition of a
    Chung-Lu graph with degree exponent alpha=2.0."""
    from repro.graph.pagerank import build_partitions
    parts = build_partitions(_chung_lu(n, 2.0, edge_factor, seed), n, m,
                             seed=seed)
    return ([p.out_idx.astype("uint32") for p in parts],
            [p.in_idx.astype("uint32") for p in parts])


def phase_allreduce(m: int = 4, n: int = 1 << 16, edge_factor: int = 16,
                    seed: int = 0) -> dict:
    """Planned ``config``/``reduce`` vs the simulator and the dense oracle,
    and ``union_reduce`` under every merge vs a sort oracle, at degrees
    (m,) and (2,2)."""
    import numpy as np
    from repro.core import SparseAllreduce
    from repro.core.simulator import dense_oracle
    from repro.core.sparse_vec import HashPerm
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((m,), ("nodes",))
    outs, ins = _power_law_sets(m, n, edge_factor, seed)
    rng = np.random.RandomState(seed)
    vals = [rng.randn(len(o)) for o in outs]
    res = {"ok": True, "out_nnz": [len(o) for o in outs],
           "in_nnz": [len(i) for i in ins]}

    # union-path input: each node's hashed, sorted, SENTINEL-padded chunk
    cap = max(len(o) for o in outs)
    u_idx = np.full((m, cap), 0xFFFFFFFF, np.uint32)
    u_val = np.zeros((m, cap), np.float32)
    perm = HashPerm.make(seed)
    for i, o in enumerate(outs):
        u_idx[i, : len(o)] = np.sort(perm.fwd_np(o))
        u_val[i, : len(o)] = _lattice(rng, (len(o),))
    keep = u_idx != 0xFFFFFFFF
    want_i, inv = np.unique(u_idx[keep], return_inverse=True)
    want_v = np.zeros(len(want_i), np.float64)
    np.add.at(want_v, inv, u_val[keep])    # exact: <= m lattice values
    res["union_unique"] = len(want_i)

    for degrees in ((m,), (2, 2)):
        tag = "x".join(map(str, degrees))
        sim = SparseAllreduce(m, degrees, backend="sim", seed=seed,
                              plan_cache=False)
        dev = SparseAllreduce(m, degrees, backend="device", seed=seed,
                              mesh=mesh, plan_cache=False)
        sim.config(outs, ins)
        dev.config(outs, ins)
        got, by_sim = dev.reduce(vals), sim.reduce(vals)
        want = dense_oracle(outs, vals, ins, dev.perm)
        ok = all(np.allclose(g, w, rtol=1e-5, atol=1e-5)
                 and np.allclose(g, s, rtol=1e-5, atol=1e-5)
                 for g, w, s in zip(got, want, by_sim))
        err = max(float(np.max(np.abs(g - w), initial=0.0))
                  for g, w in zip(got, want))
        res[f"planned_{tag}"] = {"ok": ok, "max_abs_err": err}
        res["ok"] = res["ok"] and ok
        sort_out = None
        for merge in ("sort", "fused", "banded"):
            ar = SparseAllreduce(m, degrees, backend="device", mesh=mesh,
                                 merge=merge, plan_cache=False)
            oi, ov, ovf = ar.union_reduce(u_idx, u_val, m * cap)
            oi, ov = np.asarray(oi), np.asarray(ov)
            ok = int(np.asarray(ovf).sum()) == 0 and all(
                np.array_equal(oi[i][oi[i] != 0xFFFFFFFF], want_i)
                and np.array_equal(ov[i][oi[i] != 0xFFFFFFFF],
                                   want_v.astype(np.float32))
                for i in range(m))
            if sort_out is None:
                sort_out = (oi, ov)
            ok = ok and np.array_equal(sort_out[0], oi) \
                and np.array_equal(sort_out[1], ov)
            res[f"union_{tag}_{merge}"] = {"ok": ok}
            res["ok"] = res["ok"] and ok
    return res


def phase_train_4(batch: int = 8, seq: int = 512, steps: int = 3) -> dict:
    """Data-parallel training on 4 chips: sparse sync matches ring sync,
    and state and batches are created sharded across all 4 devices."""
    import dataclasses

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.launch.train import init_sharded_state
    from repro.train.step import _ns, make_train_step
    cfg = dataclasses.replace(get_config(ARCH), tie_embeddings=False)
    mesh = make_mesh((4, 1), ("data", "model"))
    _, specs = make_train_step(cfg, mesh, sync="ring")
    params, opt = init_sharded_state(cfg, mesh)
    leaves = jax.tree.leaves((params, opt))
    state_on_all = all(len(x.sharding.device_set) == 4 for x in leaves)
    tok = jax.device_put(np.zeros((batch, seq), np.int32),
                         _ns(mesh, specs["batch"])["tokens"])
    rows = sorted({s.data.shape[0] for s in tok.addressable_shards})
    batch_split = rows == [batch // 4] and \
        len({s.device for s in tok.addressable_shards}) == 4
    del params, opt, leaves, tok
    sparse = _launch_train(batch, seq, steps, 4, "sparse")
    ring = _launch_train(batch, seq, steps, 4, "ring")
    close = bool(np.allclose(sparse["losses"], ring["losses"], rtol=1e-3))
    return {"ok": sparse["ok"] and ring["ok"] and close and state_on_all
            and batch_split, "sparse": sparse["losses"],
            "ring": ring["losses"], "state_on_all_devices": state_on_all,
            "batch_rows_per_device": rows}


# ---------------------------------------------------------------------------

def _run(name: str, fn, clock: _CompileClock, failed: list, **kw) -> None:
    clock.reset()
    t0 = time.time()
    try:
        res = fn(**kw)
    except Exception:  # a failed phase is reported, then the run fails
        traceback.print_exc()
        res = {"ok": False, "error": traceback.format_exc(limit=1)[-400:]}
    res["wall_s"] = round(time.time() - t0, 3)
    res["compile_s"] = round(clock.seconds, 3)
    if not res["ok"]:
        failed.append(name)
    print(f"phase {name}: {'PASS' if res['ok'] else 'FAIL'} "
          f"{json.dumps(res, default=str)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip paths, on four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    os.environ.setdefault("REPRO_PLAN_CACHE", os.path.join(ROOT, ".plan_cache"))
    import jax
    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s), JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    clock = _CompileClock()
    failed: list = []
    if args.four_chips:
        _run("allreduce4", phase_allreduce, clock, failed)
        _run("graph4", phase_graph, clock, failed, m=4, degrees=(2, 2),
             edges=_chung_lu(1 << 16, 2.2, 16, 0), n=1 << 16)
        _run("train4", phase_train_4, clock, failed)
    else:
        _run("train", phase_train, clock, failed)
        _run("serve", phase_serve, clock, failed)
        _run("graph", phase_graph, clock, failed)
        _run("merge", phase_merge, clock, failed)
    d = jax.devices()[0]
    result = {"ok": not failed,
              "device": {"platform": d.platform, "kind": d.device_kind,
                         "count": len(jax.devices())}}
    if failed:
        result["failed"] = failed
    print(json.dumps(result), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
