"""Multi-device shard_map allreduce correctness (subprocess: 8 CPU devices).

Per the brief, the main pytest process stays single-device; these tests
spawn one subprocess each with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys

import pytest

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src")
            + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", code], env=_ENV,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


UNION_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.core.allreduce import make_device_plan, run_union_allreduce
from repro.core.sparse_vec import HashPerm

rng = np.random.RandomState(1)
M, C, R = 8, 64, 4096
perm = HashPerm.make(7)
idx = np.full((M, C), 0xFFFFFFFF, np.uint32)
val = np.zeros((M, C), np.float32)
acc = {}
for n in range(M):
    nn = rng.randint(10, C // 2)
    oi = rng.choice(R, size=nn, replace=False).astype(np.uint32)
    ov = rng.randn(nn).astype(np.float32)
    h = perm.fwd_np(oi); order = np.argsort(h)
    idx[n, :nn] = h[order]; val[n, :nn] = ov[order]
    for j in range(nn):
        acc[int(h[j])] = acc.get(int(h[j]), 0.0) + float(ov[j])
want_idx = np.array(sorted(acc), np.uint32)
want_val = np.array([acc[int(k)] for k in want_idx])
mesh = make_mesh((8,), ("d",))
for degs in [(4, 2), (2, 2, 2), (8,), (2, 4)]:
    plan = make_device_plan([("d", 8)], {"d": degs}, in_capacity=C,
                            out_capacity=M * C)
    oi, ov, ovf = run_union_allreduce(mesh, plan, jnp.asarray(idx),
                                      jnp.asarray(val))
    oi, ov = np.asarray(oi), np.asarray(ov)
    assert np.asarray(ovf).sum() == 0
    for n in range(M):
        m = oi[n] != 0xFFFFFFFF
        assert np.array_equal(oi[n][m], want_idx), degs
        np.testing.assert_allclose(ov[n][m], want_val, rtol=1e-5)
print("UNION_OK")
"""


PLANNED_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.core.allreduce import make_device_plan
from repro.core.planned import plan_sparse_allreduce
from repro.core.simulator import dense_oracle
from repro.core.sparse_vec import HashPerm

rng = np.random.RandomState(3)
M, R = 8, 3000
perm = HashPerm.make(11)
out_idx = [rng.randint(0, R, rng.randint(30, 120)).astype(np.uint32)
           for _ in range(M)]
out_val = [rng.randn(len(o)).astype(np.float32) for o in out_idx]
in_idx = [rng.choice(R, rng.randint(20, 90), replace=False).astype(np.uint32)
          for _ in range(M)]
mesh = make_mesh((8,), ("d",))
oracle = dense_oracle(out_idx, out_val, in_idx, perm)
for degs in [(4, 2), (8,)]:
    dplan = make_device_plan([("d", 8)], {"d": degs}, 128, 1024)
    p = plan_sparse_allreduce(dplan, out_idx, in_idx, perm=perm)
    fn = p.make_reduce_fn(mesh)
    u = p.user_scatter.shape[1]
    vals = np.zeros((M, u), np.float32)
    for n in range(M):
        vals[n, :len(out_val[n])] = out_val[n]
    out = np.asarray(fn(jnp.asarray(vals)))
    for n in range(M):
        np.testing.assert_allclose(out[n, :len(in_idx[n])], oracle[n],
                                   rtol=1e-5, atol=1e-6)
    # reduce again with fresh values (config reused)
    vals2 = vals * 2.0
    out2 = np.asarray(fn(jnp.asarray(vals2)))
    for n in range(M):
        np.testing.assert_allclose(out2[n, :len(in_idx[n])],
                                   [2*x for x in oracle[n]], rtol=1e-5,
                                   atol=1e-6)
print("PLANNED_OK")
"""


DENSE_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from repro.compat import shard_map
from jax.sharding import PartitionSpec as P
from repro.core.allreduce import (dense_allreduce_binary,
                                  dense_allreduce_hierarchical,
                                  make_device_plan)

mesh = make_mesh((8,), ("d",))
x = np.random.RandomState(0).randn(8, 64).astype(np.float32)
want = x.sum(0)
plan = make_device_plan([("d", 8)], {"d": (4, 2)}, 8, 8)

def body(v):
    h = dense_allreduce_hierarchical(v[0], plan)
    b = dense_allreduce_binary(v[0], "d", 8)
    r = lax.psum(v[0], "d")
    return h[None], b[None], r[None]

fn = shard_map(body, mesh=mesh, in_specs=P("d"),
               out_specs=(P("d"), P("d"), P("d")), check_vma=False)
h, b, r = fn(jnp.asarray(x))
for got in (h, b, r):
    for n in range(8):
        np.testing.assert_allclose(np.asarray(got)[n], want, rtol=1e-5)
print("DENSE_OK")
"""


SYNC_MODES_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import transformer as T
from repro.optim.adamw import AdamW
from repro.train.step import make_train_step

cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                          tie_embeddings=False)
mesh = make_mesh((4, 2), ("data", "model"))
params0 = T.init_params(cfg, tp=2, seed=0)
rng = np.random.RandomState(0)
B, S = 8, 32
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)}
results = {}
for sync in ("ring", "hier", "sparse"):
    step, _ = make_train_step(cfg, mesh, sync=sync, donate=False)
    p, o, m = step(params0, AdamW().init(params0), batch)
    results[sync] = (jax.tree.leaves(p), float(m["loss"]),
                     float(m["sync_overflow"]))
assert results["sparse"][2] == 0.0, "sparse sync overflowed"
for sync in ("hier", "sparse"):
    assert abs(results[sync][1] - results["ring"][1]) < 1e-5
    for a, b in zip(results[sync][0], results["ring"][0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
print("SYNC_MODES_OK")
"""


MICROBATCH_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import transformer as T
from repro.optim.adamw import AdamW
from repro.train.step import make_train_step

cfg = get_config("qwen1.5-0.5b").reduced()
mesh = make_mesh((2, 2), ("data", "model"))
params0 = T.init_params(cfg, tp=2, seed=0)
rng = np.random.RandomState(0)
B, S = 8, 32
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32),
         "labels": jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)}
outs = {}
for micro in (1, 4):
    step, _ = make_train_step(cfg, mesh, donate=False, microbatch=micro)
    p, o, m = step(params0, AdamW().init(params0), batch)
    outs[micro] = (jax.tree.leaves(p), float(m["loss"]))
assert abs(outs[1][1] - outs[4][1]) < 1e-4
for a, b in zip(outs[1][0], outs[4][0]):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                               atol=3e-5)
print("MICROBATCH_OK")
"""


@pytest.mark.slow
def test_union_allreduce_8dev():
    assert "UNION_OK" in _run(UNION_CODE)


@pytest.mark.slow
def test_planned_allreduce_8dev():
    assert "PLANNED_OK" in _run(PLANNED_CODE)


@pytest.mark.slow
def test_dense_baselines_8dev():
    assert "DENSE_OK" in _run(DENSE_CODE)


@pytest.mark.slow
def test_grad_sync_modes_equivalent_8dev():
    """ring / hier / sparse sync produce the same update (the paper's
    primitive is a drop-in replacement for psum)."""
    assert "SYNC_MODES_OK" in _run(SYNC_MODES_CODE)


@pytest.mark.slow
def test_microbatch_accumulation_equivalent():
    assert "MICROBATCH_OK" in _run(MICROBATCH_CODE)


SERVE2D_CODE = r"""
from repro.launch.mesh import make_mesh
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import transformer as T
from repro.train.step import make_decode_step, make_prefill_step

cfg = dataclasses.replace(get_config("command-r-plus-104b").reduced(),
                          fsdp=True)
mesh = make_mesh((2, 2), ("data", "model"))
params = T.init_params(cfg, tp=2, seed=0)
rng = np.random.RandomState(0)
B, S, MAX = 4, 12, 16
prefill, _ = make_prefill_step(cfg, mesh, max_seq=MAX)
toks = rng.randint(0, cfg.vocab, (B, S)).astype(np.int32)
logits, cache = prefill(params, {"tokens": jnp.asarray(toks)})
tok = jnp.asarray(np.argmax(np.asarray(logits), -1), jnp.int32)
pos = jnp.full((B,), S, jnp.int32)
lg_g, _ = make_decode_step(cfg, mesh, serve2d=False)[0](params, tok, pos, cache)
lg_2, _ = make_decode_step(cfg, mesh, serve2d=True)[0](params, tok, pos, cache)
np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_2),
                           rtol=2e-3, atol=2e-3)

# MoE + hybrid variants (moe_ffn_2d / mamba_decode_2d)
for arch in ("arctic-480b", "jamba-1.5-large-398b"):
    cfg2 = dataclasses.replace(get_config(arch).reduced(), fsdp=True)
    params2 = T.init_params(cfg2, tp=2, seed=0)
    pf2, _ = make_prefill_step(cfg2, mesh, max_seq=MAX)
    lg0, cache0 = pf2(params2, {"tokens": jnp.asarray(toks)})
    t0 = jnp.asarray(np.argmax(np.asarray(lg0), -1), jnp.int32)
    g0, _ = make_decode_step(cfg2, mesh, serve2d=False)[0](
        params2, t0, pos, cache0)
    s0, _ = make_decode_step(cfg2, mesh, serve2d=True)[0](
        params2, t0, pos, cache0)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(s0),
                               rtol=5e-3, atol=5e-3)

# seq-sharded (long-context) variant: batch replicated, cache over data
from repro.train.step import init_cache_global, mesh_ctx
mc = mesh_ctx(mesh)
cache2 = init_cache_global(cfg, mc, 2, 16)
cache2 = jax.tree.map(
    lambda x: jnp.asarray(np.random.RandomState(1).randn(*x.shape),
                          x.dtype) * 0.1, cache2)
tok2 = jnp.asarray(np.random.RandomState(2).randint(0, cfg.vocab, (2,)),
                   jnp.int32)
pos2 = jnp.full((2,), 5, jnp.int32)
g2, _ = make_decode_step(cfg, mesh, seq_sharded=True, seq_shards=2)[0](
    params, tok2, pos2, cache2)
s2, _ = make_decode_step(cfg, mesh, seq_sharded=True, seq_shards=2,
                         serve2d=True)[0](params, tok2, pos2, cache2)
np.testing.assert_allclose(np.asarray(g2), np.asarray(s2), rtol=3e-3,
                           atol=3e-3)
print("SERVE2D_OK")
"""


@pytest.mark.slow
def test_serve2d_matches_gather_decode():
    """2D weight-stationary decode (SPerf H4) == gather-mode decode."""
    assert "SERVE2D_OK" in _run(SERVE2D_CODE)


KERNEL_UNION_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.core.allreduce import make_device_plan, run_union_allreduce
from repro.core.sparse_vec import HashPerm

rng = np.random.RandomState(5)
M, C, R = 8, 48, 2048
perm = HashPerm.make(9)
idx = np.full((M, C), 0xFFFFFFFF, np.uint32)
val = np.zeros((M, C), np.float32)
for n in range(M):
    nn = rng.randint(8, C // 2)
    oi = rng.choice(R, nn, replace=False).astype(np.uint32)
    h = perm.fwd_np(oi); o = np.argsort(h)
    idx[n, :nn] = h[o]; val[n, :nn] = rng.randn(nn)
mesh = make_mesh((8,), ("d",))
plan = make_device_plan([("d", 8)], {"d": (4, 2)}, C, M * C)
oi1, ov1, _ = run_union_allreduce(mesh, plan, jnp.asarray(idx),
                                  jnp.asarray(val), use_kernel=False)
oi2, ov2, _ = run_union_allreduce(mesh, plan, jnp.asarray(idx),
                                  jnp.asarray(val), use_kernel=True)
np.testing.assert_array_equal(np.asarray(oi1), np.asarray(oi2))
np.testing.assert_allclose(np.asarray(ov1), np.asarray(ov2), rtol=1e-5,
                           atol=1e-6)
print("KERNEL_UNION_OK")
"""


@pytest.mark.slow
def test_pallas_kernel_inside_union_allreduce():
    """MXU segment-compact kernel composes with the butterfly collectives."""
    assert "KERNEL_UNION_OK" in _run(KERNEL_UNION_CODE)


FUSED_UNION_CODE = r"""
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.core.api import SparseAllreduce
from repro.core.sparse_vec import HashPerm

rng = np.random.RandomState(2)
M, C, R = 8, 48, 2048
perm = HashPerm.make(9)
idx = np.full((M, C), 0xFFFFFFFF, np.uint32)
val = np.zeros((M, C), np.float32)
acc = {}
for n in range(M):
    nn = rng.randint(8, C // 2)
    oi = rng.choice(R, nn, replace=False).astype(np.uint32)
    ov = rng.randn(nn).astype(np.float32)
    h = perm.fwd_np(oi); o = np.argsort(h)
    idx[n, :nn] = h[o]; val[n, :nn] = ov[o]
    for j in range(nn):
        acc[int(h[j])] = acc.get(int(h[j]), 0.0) + float(ov[j])
want_idx = np.array(sorted(acc), np.uint32)
want_val = np.array([acc[int(k)] for k in want_idx])
mesh = make_mesh((8,), ("d",))
outs = {}
for merge in ("sort", "fused", "banded"):
    ar = SparseAllreduce(8, (4, 2), backend="device", mesh=mesh, merge=merge)
    oi, ov, ovf = ar.union_reduce(jnp.asarray(idx), jnp.asarray(val),
                                  out_capacity=M * C)
    assert np.asarray(ovf).sum() == 0, merge
    oi, ov = np.asarray(oi), np.asarray(ov)
    for n in range(M):
        m = oi[n] != 0xFFFFFFFF
        assert np.array_equal(oi[n][m], want_idx), merge
        np.testing.assert_allclose(ov[n][m], want_val, rtol=1e-5)
    outs[merge] = (oi, ov)
for other in ("fused", "banded"):
    np.testing.assert_array_equal(outs["sort"][0], outs[other][0])
    np.testing.assert_array_equal(outs["sort"][1], outs[other][1])
print("FUSED_UNION_OK")
"""


@pytest.mark.slow
def test_fused_merge_union_allreduce_8dev():
    """merge='fused' / merge='banded' (Pallas rank-merge pipelines) ==
    merge='sort' through the full nested butterfly, selected via the
    SparseAllreduce knob."""
    assert "FUSED_UNION_OK" in _run(FUSED_UNION_CODE)
