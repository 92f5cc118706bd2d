"""Serving-tier benchmarks (``--only serve``; PR 10).

Three row families over the continuous-batching decode service
(``repro.serve``), all on the deterministic virtual clock so the derived
columns are reproducible (wall time feeds only tokens/s):

* ``serve/load_*`` — tokens/s and p50/p99 request latency (in decode
  steps) vs offered load at 0.5x / 1x / 2x the sustainable rate
  (``slots / mean_new_tokens`` requests per step), with admission
  control on.  The 2x row is the saturation contract: the service
  *sheds* (``shed > 0``) instead of queueing unboundedly, and the p99
  of **admitted** requests stays within the SLO.
* ``serve/plan_cache_churn`` — sparse-dispatch plan-cache hit rate over
  batch-shape churn (joins/evictions vary the per-step tail size; the
  power-of-two ``shape_bucket`` keys keep the compiled-pipeline cache
  small).  The committed floor is 0.8.
* ``serve/dispatch_wire_*`` — per-step cost of the hot/cold sparse
  exchange with the tail union on ``raw`` vs a PR-8 compressed codec.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]

SLOTS = 4
PROMPT_LENS = (4, 8, 6)
MAX_NEW = (3, 9)          # mean 6 -> sustainable ~ SLOTS/6 req per step
SLO_STEPS = 64.0


def _scheduler(dispatch=None):
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import transformer as T
    from repro.serve import ContinuousBatchingScheduler

    cfg = get_config("qwen1.5-0.5b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init_params(cfg, tp=1, seed=0)
    sched = ContinuousBatchingScheduler(
        cfg, mesh, params, slots=SLOTS,
        max_seq=max(PROMPT_LENS) + MAX_NEW[1] + 1, dispatch=dispatch)
    return cfg, sched


def _stream(cfg, n, rate, seed, eos=None):
    from repro.serve import zipf_request_stream
    return zipf_request_stream(n, cfg.vocab, prompt_lens=PROMPT_LENS,
                               max_new=MAX_NEW, arrival_rate=rate,
                               eos_id=eos, seed=seed)


def bench_serve_load_latency() -> List[Row]:
    """tokens/s + p50/p99 vs offered load; shed-not-queue at saturation."""
    from repro.serve import AdmissionController, DecodeService

    cfg, sched = _scheduler()
    sustainable = SLOTS / (0.5 * (MAX_NEW[0] + MAX_NEW[1]))
    # warm the per-prompt-length prefill and decode compiles so the row
    # wall times compare service throughput, not XLA compilation
    DecodeService(sched).run(_stream(cfg, n=6, rate=None, seed=99))
    rows: List[Row] = []
    for factor in (0.5, 1.0, 2.0):
        sched.reset()
        adm = AdmissionController(
            rate=sustainable, burst=float(SLOTS), queue_cap=2 * SLOTS,
            slo=SLO_STEPS, breach_window=8, cooldown=32.0)
        reqs = _stream(cfg, n=40, rate=factor * sustainable,
                       seed=int(10 * factor))
        report = DecodeService(sched, adm).run(reqs)
        s = report.admission
        us_per_step = report.wall_s * 1e6 / max(report.steps, 1)
        within = report.p99_steps <= SLO_STEPS
        rows.append((
            f"serve/load_{factor:g}x", us_per_step,
            f"tok_s={report.tokens_per_s:.0f} p50={report.p50_steps:.0f} "
            f"p99={report.p99_steps:.0f} offered={s.offered} "
            f"admitted={s.admitted} shed={s.shed} "
            f"admitted_p99_within_slo={within}"))
        if factor >= 2.0:
            assert s.shed > 0, "2x load must shed, not queue unboundedly"
            assert within, (
                f"admitted p99 {report.p99_steps} exceeds SLO {SLO_STEPS}")
    return rows


def bench_serve_plan_cache_churn() -> List[Row]:
    """Plan-cache hit rate across batch-shape churn (floor 0.8)."""
    from repro.serve import DecodeService
    from repro.serve.dispatch import SparseServeDispatch

    disp = SparseServeDispatch(1, vocab=512, seed=7)
    cfg, sched = _scheduler(dispatch=disp)
    reqs = _stream(cfg, n=32, rate=0.8, seed=5)
    disp.fit_hot_set(np.concatenate([r.prompt for r in reqs]), head_size=8)
    t0 = time.perf_counter()
    report = DecodeService(sched).run(reqs)
    dt_us = (time.perf_counter() - t0) * 1e6
    hit = report.plan_hit_rate
    u = disp._tail_ar.union_plan_stats
    assert hit is not None and hit >= 0.8, f"plan hit rate {hit} < 0.8"
    return [(
        "serve/plan_cache_churn", dt_us / max(disp.steps, 1),
        f"hit_rate={hit:.3f} frozen={disp.frozen_reduces} "
        f"union_hits={u['hits']} union_misses={u['misses']} "
        f"steps={disp.steps}")]


def bench_serve_dispatch_wire() -> List[Row]:
    """Per-step hot/cold exchange cost, tail union raw vs compressed."""
    from repro.data.pipeline import zipf_tokens
    from repro.serve.dispatch import SparseServeDispatch

    rows: List[Row] = []
    rng = np.random.RandomState(11)
    warm = zipf_tokens(rng, (1, 4096), 4096, alpha=1.2)[0]
    for wire in ("raw", "delta+int8ef"):
        disp = SparseServeDispatch(1, vocab=4096, wire=wire, seed=3)
        disp.fit_hot_set(warm, head_size=64)
        shards = [zipf_tokens(rng, (1, SLOTS), 4096, alpha=1.2)[0]
                  for _ in range(12)]
        disp.on_step([shards[0]])          # warm the union compile
        t0 = time.perf_counter()
        for s in shards[1:]:
            disp.on_step([s])
        dt_us = (time.perf_counter() - t0) * 1e6 / (len(shards) - 1)
        ex = disp.last
        rows.append((
            f"serve/dispatch_wire_{wire}", dt_us,
            f"head={len(ex.head_ids)} tail={len(ex.tail_ids)} "
            f"hit_rate={disp.plan_hit_rate:.3f}"))
    return rows


ALL_BENCHES = [bench_serve_load_latency, bench_serve_plan_cache_churn,
               bench_serve_dispatch_wire]
