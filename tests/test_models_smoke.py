"""Per-arch REDUCED smoke tests: one forward/train step + prefill/decode on
CPU, asserting output shapes and finiteness (full configs only via dry-run).
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, pair_plan, all_pairs
from repro.models import transformer as T
from repro.optim.adamw import AdamW
from repro.train.step import (make_decode_step, make_prefill_step,
                              make_train_step, mesh_ctx)
from repro.launch.mesh import make_mesh

warnings.filterwarnings("ignore")
B, S, MAX = 2, 32, 48


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _batch(cfg, rng, with_labels=True):
    out = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)}
    if with_labels:
        out["labels"] = jnp.asarray(rng.randint(0, cfg.vocab, (B, S)),
                                    jnp.int32)
    if cfg.img_tokens:
        out["img_embeds"] = jnp.asarray(
            rng.randn(B, cfg.img_tokens, cfg.d_model), jnp.float32)
    if cfg.enc_layers:
        out["enc_frames"] = jnp.asarray(
            rng.randn(B, cfg.enc_seq, cfg.d_model), jnp.float32)
    return out


@pytest.mark.parametrize("arch", list(ARCHS))
def test_reduced_train_step(arch, mesh):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers <= 2 * len(cfg.pattern) and cfg.d_model <= 512
    assert (cfg.n_experts or 0) <= 4
    rng = np.random.RandomState(0)
    step, _ = make_train_step(cfg, mesh, donate=False)
    params = T.init_params(cfg, tp=1, seed=0)
    opt = AdamW().init(params)
    p1, o1, m1 = step(params, opt, _batch(cfg, rng))
    assert np.isfinite(float(m1["loss"]))
    assert float(m1["loss"]) > 0
    p2, o2, m2 = step(p1, o1, _batch(cfg, rng))
    assert np.isfinite(float(m2["loss"]))
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p1)))
    assert delta > 0


@pytest.mark.parametrize("arch", list(ARCHS))
def test_reduced_prefill_decode(arch, mesh):
    cfg = get_config(arch).reduced()
    mc = mesh_ctx(mesh)
    rng = np.random.RandomState(1)
    params = T.init_params(cfg, tp=1, seed=0)
    prefill, _ = make_prefill_step(cfg, mesh, max_seq=MAX)
    logits, cache = prefill(params, _batch(cfg, rng, with_labels=False))
    vp = T.padded_vocab(cfg, 1)
    assert logits.shape == (B, vp)
    assert np.all(np.isfinite(np.asarray(logits)))

    decode, _ = make_decode_step(cfg, mesh)
    extra = ()
    if cfg.enc_layers:
        from repro.compat import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.models.sharding import full_model_pspec
        ax = mc.axis_ctx(cfg)
        frames = _batch(cfg, rng, with_labels=False)["enc_frames"]
        ccfn = shard_map(
            lambda p, f: T.build_cross_cache(p, f, cfg, ax), mesh=mesh,
            in_specs=(full_model_pspec(cfg, mc.tp, mc.dp_axes), P("data")),
            out_specs=(P(None, "data", None, "model", None),
                       P(None, "data", None, "model", None)),
            check_vma=False)
        extra = (ccfn(params, frames),)
    tok = jnp.asarray(np.argmax(np.asarray(logits)[:, :cfg.vocab], -1),
                      jnp.int32)
    pos = jnp.full((B,), S + (cfg.img_tokens or 0), jnp.int32)
    lg, cache2 = decode(params, tok, pos, cache, *extra)
    assert lg.shape == (B, vp)
    assert np.all(np.isfinite(np.asarray(lg)))
    # cache must have been written
    changed = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(cache),
                                  jax.tree.leaves(cache2)))
    assert changed > 0


def test_pair_plan_covers_40():
    pairs = all_pairs()
    assert len(pairs) == 40
    skips = [(a, s) for a, s, v in pairs if v is None]
    assert skips == [("internvl2-26b", "long_500k"),
                     ("whisper-base", "long_500k")]
    swa = [a for a, s, v in pairs if v == "swa"]
    assert "command-r-plus-104b" in swa and "qwen1.5-0.5b" in swa


def test_param_counts_in_expected_range():
    expect = {"starcoder2-15b": (13e9, 22e9),
              "jamba-1.5-large-398b": (300e9, 480e9),
              "gemma3-12b": (8e9, 16e9),
              "qwen1.5-0.5b": (0.4e9, 0.8e9),
              "arctic-480b": (380e9, 560e9),
              "command-r-plus-104b": (85e9, 135e9),
              "xlstm-1.3b": (0.8e9, 2.0e9),
              "whisper-base": (0.05e9, 0.12e9)}
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.1f}B outside [{lo/1e9},{hi/1e9}]"
