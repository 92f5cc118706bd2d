"""Request-level consistency harness for the serving tier
(ARCHITECTURE.md "Serving tier").

The claim under test: continuous-batched decode returns **token-for-token
exactly** what each request would get served alone.  The oracle is the
same scheduler instance run one-request-at-a-time
(``run_sequential_oracle``) — same compiled slot geometry, so equality
isolates request isolation (slot writes, position tracking, join/evict
bookkeeping) from XLA's batch-size-dependent reduction order, which is
*not* bitwise across different compiled batch sizes.

Tiers:

* always-on: the consistency sweep over slot counts {1, 2, 8} with
  seeded Zipf streams (mixed prompt lengths, staggered arrivals,
  ``max_new`` churn incl. join-completes), EOS eviction, dispatch
  non-perturbation + numpy-oracle agreement of the sparse exchange, the
  expert-load path, scheduler validation, and the ``audit_serve_decode``
  pinned regression (the fused greedy steps pass; the raw logits-
  returning decode step must *fail* — the check has teeth).
* ``@pytest.mark.slow`` subprocess: the 16-device case — mesh (8, 2),
  granite-moe reduced, slots=8 over dp=8 shards, sparse dispatch on —
  batched == oracle and every step's exchange equals a dense bincount.
"""
import os
import subprocess
import sys
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import (ContinuousBatchingScheduler, DecodeService,
                         zipf_request_stream)
from repro.serve.service import run_sequential_oracle
from repro.launch.mesh import make_mesh

warnings.filterwarnings("ignore")

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_ENV16 = dict(os.environ,
              XLA_FLAGS="--xla_force_host_platform_device_count=16",
              PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))

MAX_SEQ = 24


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    return cfg, T.init_params(cfg, tp=1, seed=0)


def _stream(cfg, n, seed, eos_id=None):
    """Mixed prompt lengths, staggered arrivals, max_new down to 1 (a
    request that completes at join, exercising the no-decode path)."""
    return zipf_request_stream(
        n, cfg.vocab, prompt_lens=(4, 8, 6), max_new=(1, 7),
        arrival_rate=0.6, eos_id=eos_id, seed=seed)


def _serve_and_compare(cfg, mesh, params, slots, reqs, dispatch=None):
    sched = ContinuousBatchingScheduler(
        cfg, mesh, params, slots=slots, max_seq=MAX_SEQ, dispatch=dispatch)
    report = DecodeService(sched).run(reqs)
    assert len(report.completed) == len(reqs)
    batched = {r.rid: list(r.tokens) for r in report.completed}
    sched.reset()
    oracle = run_sequential_oracle(sched, reqs)
    for i, req in enumerate(reqs):
        assert batched[req.rid] == oracle[i], \
            f"rid {req.rid} (slots={slots}): {batched[req.rid]} != {oracle[i]}"
    return batched, report


# ---------------------------------------------------------------------------
# The consistency sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slots", [1, 2, 8])
def test_continuous_batching_matches_sequential_oracle(qwen, mesh, slots):
    cfg, params = qwen
    reqs = _stream(cfg, n=7, seed=100 + slots)
    batched, report = _serve_and_compare(cfg, mesh, params, slots, reqs)
    # every request generated something and respected its budget
    for req in reqs:
        assert 1 <= len(batched[req.rid]) <= req.max_new
    assert report.tokens_out == sum(len(t) for t in batched.values())


def test_eos_evicts_early_and_stays_consistent(qwen, mesh):
    """Pick a token the model actually emits mid-request, declare it EOS,
    and re-serve: the request must stop at it (strictly early), and the
    batched run must still match the oracle token-for-token."""
    cfg, params = qwen
    probe = _stream(cfg, n=5, seed=7)
    sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=2,
                                        max_seq=MAX_SEQ)
    DecodeService(sched).run(probe)
    eos = next((r.tokens[1] for r in probe if len(r.tokens) >= 3), None)
    assert eos is not None, "probe stream produced no 3-token request"

    reqs = _stream(cfg, n=5, seed=7, eos_id=int(eos))
    sched.reset()
    report = DecodeService(sched).run(reqs)
    batched = {r.rid: list(r.tokens) for r in report.completed}
    sched.reset()
    oracle = run_sequential_oracle(sched, reqs)
    stopped_early = 0
    for i, req in enumerate(reqs):
        assert batched[req.rid] == oracle[i]
        if len(batched[req.rid]) < req.max_new:
            assert batched[req.rid][-1] == eos
            stopped_early += 1
    assert stopped_early >= 1, "EOS never fired — eviction path untested"


def test_scheduler_validation_and_slot_bookkeeping(qwen, mesh):
    cfg, params = qwen
    with pytest.raises(ValueError, match="decoder-only"):
        ContinuousBatchingScheduler(get_config("whisper-base").reduced(),
                                    mesh, params, slots=2, max_seq=MAX_SEQ)
    with pytest.raises(ValueError, match="multiple"):
        ContinuousBatchingScheduler(cfg, mesh, params, slots=0,
                                    max_seq=MAX_SEQ)
    sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=2,
                                        max_seq=MAX_SEQ)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        sched.join(zipf_request_stream(1, cfg.vocab,
                                       prompt_lens=(MAX_SEQ,),
                                       max_new=(4, 4), seed=0)[0])
    reqs = zipf_request_stream(3, cfg.vocab, prompt_lens=(4,),
                               max_new=(3, 3), seed=1)
    assert sched.join(reqs[0]) == 0 and sched.join(reqs[1]) == 1
    assert sched.free_slots() == [] and sched.active == 2
    with pytest.raises(RuntimeError, match="no free slot"):
        sched.join(reqs[2])
    while sched.active:
        sched.step()
    done = sched.pop_completed()
    assert sorted(r.rid for r in done) == [0, 1]
    assert sched.free_slots() == [0, 1]
    assert sched.metrics.joins == 2 and sched.metrics.evictions == 2


# ---------------------------------------------------------------------------
# Sparse dispatch: non-perturbation + exchange correctness
# ---------------------------------------------------------------------------

class _RecordingDispatch:
    """Wraps SparseServeDispatch to capture (input shards, exchange)."""

    def __init__(self, inner):
        self._inner = inner
        self.trace = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def on_step(self, tok_shards):
        ex = self._inner.on_step(tok_shards)
        self.trace.append(([np.array(s) for s in tok_shards], ex))
        return ex


def test_dispatch_observes_without_perturbing_and_matches_bincount(
        qwen, mesh):
    from repro.serve.dispatch import SparseServeDispatch
    cfg, params = qwen
    reqs = _stream(cfg, n=6, seed=21)
    base, _ = _serve_and_compare(cfg, mesh, params, 2, reqs)

    disp = SparseServeDispatch(1, vocab=cfg.vocab, seed=5)
    disp.fit_hot_set(np.concatenate([r.prompt for r in reqs]), head_size=8)
    rec = _RecordingDispatch(disp)
    reqs2 = _stream(cfg, n=6, seed=21)
    sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=2,
                                        max_seq=MAX_SEQ, dispatch=rec)
    report = DecodeService(sched).run(reqs2)
    withd = {r.rid: list(r.tokens) for r in report.completed}
    assert withd == base, "enabling dispatch changed generated tokens"

    assert rec.trace, "dispatch never invoked"
    for shards, ex in rec.trace:
        toks = np.concatenate(shards).astype(np.int64)
        want = np.bincount(toks, minlength=cfg.vocab)
        got = np.zeros(cfg.vocab, np.int64)
        got[ex.head_ids.astype(np.int64)] += ex.head_counts.astype(np.int64)
        if len(ex.tail_ids):
            got[ex.tail_ids.astype(np.int64)] += \
                ex.tail_counts.astype(np.int64)
        assert ex.overflow == 0
        assert np.array_equal(got, want), "exchange != dense bincount"
        for t in toks[:4]:
            assert ex.count_of(int(t)) == want[t]
    assert report.plan_hit_rate is not None
    assert report.plan_hit_rate >= 0.5  # union cache warm after step 1


def test_expert_load_matches_predictor_oracle(mesh):
    from repro.serve.dispatch import (SparseServeDispatch, first_moe_router,
                                      make_expert_predictor)
    cfg = get_config("granite-moe-3b-a800m").reduced()
    params = T.init_params(cfg, tp=1, seed=0)
    router = first_moe_router(params)
    assert router is not None
    pred = make_expert_predictor(cfg)
    rng = np.random.RandomState(3)
    emb = params["emb"]
    ids = rng.randint(0, cfg.vocab, (10,))
    ek_shards = [np.asarray(pred(emb, router, jnp.asarray(ids)))]
    # single shard here (one host device); the 16-device subprocess case
    # exercises the 8-shard combine.
    disp = SparseServeDispatch(1, vocab=cfg.vocab, n_experts=cfg.n_experts,
                               seed=9)
    load = disp.expert_load(ek_shards)
    want = np.zeros(cfg.n_experts, np.float32)
    for ek in ek_shards:
        want += np.bincount(ek.reshape(-1),
                            minlength=cfg.n_experts).astype(np.float32)
    assert np.array_equal(load, want)
    assert load.sum() == sum(e.size for e in ek_shards)
    assert disp.plan_hit_rate == 1.0  # frozen plan only: no replanning


def test_dispatch_requires_hot_set_and_shard_agreement(qwen, mesh):
    from repro.serve.dispatch import SparseServeDispatch
    cfg, params = qwen
    disp = SparseServeDispatch(1, vocab=cfg.vocab, seed=5)
    with pytest.raises(RuntimeError, match="fit_hot_set"):
        disp.on_step([np.zeros(1, np.int32)])
    disp2 = SparseServeDispatch(2, vocab=cfg.vocab, seed=5)
    with pytest.raises(ValueError, match="shards"):
        ContinuousBatchingScheduler(cfg, mesh, params, slots=2,
                                    max_seq=MAX_SEQ, dispatch=disp2)


# ---------------------------------------------------------------------------
# Auditor pinned regression: ids-only host traffic in the decode loop
# ---------------------------------------------------------------------------

def test_audit_serve_decode_passes_fused_rejects_raw(qwen, mesh):
    from repro.analysis.auditor import audit_serve_decode
    from repro.train.step import (init_cache_global, make_decode_greedy_step,
                                  make_decode_step,
                                  make_prefill_greedy_step, mesh_ctx)
    cfg, params = qwen
    cache = init_cache_global(cfg, mesh_ctx(mesh), 2, MAX_SEQ)
    tok = jnp.zeros((2,), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)

    fused, _ = make_decode_greedy_step(cfg, mesh)
    assert audit_serve_decode("decode_greedy", fused, params, tok, pos,
                              cache, vocab=cfg.vocab).ok
    prefill, _ = make_prefill_greedy_step(cfg, mesh, MAX_SEQ)
    assert audit_serve_decode(
        "prefill_greedy", prefill, params,
        {"tokens": jnp.zeros((2, 6), jnp.int32)}, vocab=cfg.vocab).ok

    # injection: the raw decode step returns [B, V_pad] float logits —
    # serving on it would ship vocab-sized avals to host every step, and
    # the audit must refuse it on both checks.
    raw, _ = make_decode_step(cfg, mesh)
    rep = audit_serve_decode("decode_raw", raw, params, tok, pos, cache,
                             vocab=cfg.vocab)
    assert not rep.ok
    failed = {c.check_id for c in rep.failures()}
    assert "no_vocab_sized_float_output" in failed
    assert "token_ids_output_is_integer" in failed


def test_greedy_masks_padded_vocab_columns(qwen, mesh):
    """Padded logit columns are exactly 0 under tied embeddings and can
    beat all-negative real logits; the fused argmax must never pick one
    and never emit an id >= vocab."""
    cfg, params = qwen
    reqs = _stream(cfg, n=5, seed=33)
    sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=2,
                                        max_seq=MAX_SEQ)
    report = DecodeService(sched).run(reqs)
    for r in report.completed:
        assert all(0 <= t < cfg.vocab for t in r.tokens), r.tokens


# ---------------------------------------------------------------------------
# 16 forced host devices: dp=8 x tp=2, sparse dispatch over 8 shards
# ---------------------------------------------------------------------------

_CODE16 = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import transformer as T
from repro.serve import ContinuousBatchingScheduler, DecodeService
from repro.serve import zipf_request_stream
from repro.serve.dispatch import SparseServeDispatch
from repro.serve.service import run_sequential_oracle

cfg = get_config("granite-moe-3b-a800m").reduced()
mesh = make_mesh((8, 2), ("data", "model"))
params = T.init_params(cfg, tp=2, seed=0)
reqs = zipf_request_stream(10, cfg.vocab, prompt_lens=(4, 6),
                           max_new=(1, 5), arrival_rate=0.8, seed=4)
disp = SparseServeDispatch(8, vocab=cfg.vocab, n_experts=cfg.n_experts,
                           seed=11)
disp.fit_hot_set(np.concatenate([r.prompt for r in reqs]), head_size=16)
sched = ContinuousBatchingScheduler(cfg, mesh, params, slots=8,
                                    max_seq=16, dispatch=disp)
report = DecodeService(sched).run(reqs)
assert len(report.completed) == len(reqs)
batched = {r.rid: list(r.tokens) for r in report.completed}
sched.reset()
oracle = run_sequential_oracle(sched, reqs)
for i, r in enumerate(reqs):
    assert batched[r.rid] == oracle[i], (r.rid, batched[r.rid], oracle[i])
assert disp.steps > 0 and disp.plan_hit_rate > 0.0
ex = disp.last
total = float(ex.head_counts.sum() + ex.tail_counts.sum())
assert total > 0
from repro.serve.dispatch import first_moe_router, make_expert_predictor
rng = np.random.RandomState(2)
pred = make_expert_predictor(cfg)
router = first_moe_router(params)
eks = [np.asarray(pred(params["emb"], router,
                       jnp.asarray(rng.randint(0, cfg.vocab, (4,)))))
       for _ in range(8)]
load = disp.expert_load(eks)
want = sum(np.bincount(e.reshape(-1), minlength=cfg.n_experts)
           for e in eks).astype(np.float32)
assert np.array_equal(load, want), (load, want)
print("OK16", len(reqs), disp.steps, round(disp.plan_hit_rate, 3))
"""


@pytest.mark.slow
def test_serve_tier_16dev_sparse_dispatch():
    r = subprocess.run([sys.executable, "-c", _CODE16], env=_ENV16,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK16" in r.stdout
