"""Prefill->decode must agree with a longer prefill (cache correctness).

For each family: logits(decode(prefill(t[:S]), t[S])) == logits(prefill(t[:S+1])).
This catches cache-layout, position, rope, window, and state-handoff bugs
across attention / mamba / mlstm+slstm / moe blocks.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.launch.mesh import make_mesh
from repro.train.step import make_decode_step, make_prefill_step

warnings.filterwarnings("ignore")

S, MAX, B = 24, 32, 2


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch,tol", [
    ("qwen1.5-0.5b", 2e-3),          # dense GQA + bias
    ("gemma3-12b", 2e-3),            # sliding-window pattern
    ("xlstm-1.3b", 5e-2),            # mLSTM state handoff (m=0 stabilizer)
    ("granite-moe-3b-a800m", 5e-2),  # MoE routing (capacity order effects)
    ("jamba-1.5-large-398b", 5e-2),  # mamba conv tail + ssm state
])
def test_decode_matches_prefill(arch, tol, mesh):
    cfg = get_config(arch).reduced()
    rng = np.random.RandomState(0)
    params = T.init_params(cfg, tp=1, seed=0)
    toks = rng.randint(0, cfg.vocab, (B, S + 1)).astype(np.int32)

    prefill, _ = make_prefill_step(cfg, mesh, max_seq=MAX)
    decode, _ = make_decode_step(cfg, mesh)

    batch_s = {"tokens": jnp.asarray(toks[:, :S])}
    batch_s1 = {"tokens": jnp.asarray(toks)}
    if cfg.img_tokens:
        img = jnp.asarray(rng.randn(B, cfg.img_tokens, cfg.d_model),
                          jnp.float32)
        batch_s["img_embeds"] = img
        batch_s1["img_embeds"] = img

    _, cache = prefill(params, batch_s)
    pos = jnp.full((B,), S + (cfg.img_tokens or 0), jnp.int32)
    lg_decode, _ = decode(params, jnp.asarray(toks[:, S]), pos, cache)

    lg_full, _ = prefill(params, batch_s1)

    a = np.asarray(lg_decode)[:, :cfg.vocab]
    b = np.asarray(lg_full)[:, :cfg.vocab]
    # compare post-softmax (logits can differ by shared constants)
    pa = jax.nn.softmax(jnp.asarray(a), axis=-1)
    pb = jax.nn.softmax(jnp.asarray(b), axis=-1)
    err = float(jnp.max(jnp.abs(pa - pb)))
    assert err < tol, f"{arch}: softmax mismatch {err}"
    if tol < 1e-2:
        # greedy-decode invariance (loose-tol archs: near-uniform random-init
        # logits make argmax flip on float-order noise, not on cache bugs)
        assert np.array_equal(np.argmax(a, -1), np.argmax(b, -1)), arch


# ---------------------------------------------------------------------------
# Serving-tier cache-shape invariants: --model-axis x reduced archs
# ---------------------------------------------------------------------------

import os
import subprocess
import sys

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_ENV8 = dict(os.environ,
             XLA_FLAGS="--xla_force_host_platform_device_count=8",
             PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))

_CACHE_SHAPE_CODE = r"""
from repro.launch.mesh import make_mesh
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import transformer as T
from repro.models.transformer import padded_vocab
from repro.train.step import (init_cache_global, make_decode_greedy_step,
                              make_prefill_greedy_step, mesh_ctx)

MA = int(sys.argv[1])
MAX = 16
for arch in ("qwen1.5-0.5b", "granite-moe-3b-a800m", "jamba-1.5-large-398b"):
    cfg = get_config(arch).reduced()
    mesh = make_mesh((8 // MA, MA), ("data", "model"))
    mc = mesh_ctx(mesh)
    b = mc.dp
    params = T.init_params(cfg, tp=MA, seed=0)
    ref = init_cache_global(cfg, mc, b, MAX)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), ref)

    prefill, _ = make_prefill_greedy_step(cfg, mesh, MAX)
    toks = jnp.zeros((b, 6), jnp.int32)
    ids, cache = prefill(params, {"tokens": toks})
    got = jax.tree.map(lambda x: (x.shape, x.dtype), cache)
    assert got == want, (arch, "prefill cache", got, want)
    assert ids.shape == (b,) and ids.dtype == jnp.int32, (arch, ids.aval)

    decode, _ = make_decode_greedy_step(cfg, mesh)
    ids2, cache2 = decode(params, ids, jnp.full((b,), 6, jnp.int32), cache)
    got2 = jax.tree.map(lambda x: (x.shape, x.dtype), cache2)
    assert got2 == want, (arch, "decode cache", got2, want)
    assert ids2.shape == (b,) and ids2.dtype == jnp.int32
    assert int(np.asarray(ids2).max()) < cfg.vocab, arch
    # the padded tail [vocab, V_pad) must never win the greedy argmax
    assert padded_vocab(cfg, MA) % (MA * 16) == 0
print("CACHE_OK", MA)
"""


@pytest.mark.slow
@pytest.mark.parametrize("ma", [1, 2])
def test_serve_cache_shape_invariants_across_model_axis(ma):
    """The fused greedy prefill/decode steps preserve the exact cache
    tree (shapes + dtypes) that ``init_cache_global`` declares, for every
    reduced cache family (attention / MoE / mamba), under tensor
    parallelism ``--model-axis`` 1 and 2 — and their ids outputs are
    int32 in ``[0, vocab)`` (the padded-vocab tail never leaks out)."""
    r = subprocess.run([sys.executable, "-c", _CACHE_SHAPE_CODE, str(ma)],
                       env=_ENV8, capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert f"CACHE_OK {ma}" in r.stdout
