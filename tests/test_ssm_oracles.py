"""Chunked SSM formulations vs naive per-step recurrences.

The train-time mamba/mLSTM paths use chunked scans (TPU-friendly, SPerf);
these tests pin them against literal step-by-step recurrences, with
sequence lengths spanning multiple chunks (inter-chunk handoff is where
the algebra can silently break).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ssm as SSM
from repro.models.ssm import (_chunked_selective_scan, mamba_params,
                              mlstm_params)
from repro.launch.mesh import make_mesh


def test_chunked_selective_scan_vs_naive():
    rng = np.random.RandomState(0)
    B, T, dil, n = 2, SSM.SCAN_CHUNK * 2, 8, 4      # spans 2 chunks
    dt = jnp.asarray(np.abs(rng.randn(B, T, dil)) * 0.1, jnp.float32)
    xi = jnp.asarray(rng.randn(B, T, dil), jnp.float32)
    Bm = jnp.asarray(rng.randn(B, T, n), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, T, n), jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(dil, n)), jnp.float32)

    ys, h_fin = _chunked_selective_scan(dt, xi, Bm, Cm, A)

    # naive recurrence
    h = np.zeros((B, dil, n))
    ys_ref = np.zeros((B, T, dil))
    dtn, xin, Bn, Cn, An = (np.asarray(x, np.float64)
                            for x in (dt, xi, Bm, Cm, A))
    for t in range(T):
        a = np.exp(dtn[:, t][..., None] * An)
        b = (dtn[:, t] * xin[:, t])[..., None] * Bn[:, t][:, None, :]
        h = h * a + b
        ys_ref[:, t] = np.einsum("bcn,bn->bc", h, Cn[:, t])
    np.testing.assert_allclose(np.asarray(ys), ys_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_fin), h, rtol=1e-4, atol=1e-4)


def test_mamba_train_vs_stepwise_decode(monkeypatch):
    """Full-sequence mamba_train output == feeding tokens one-by-one through
    mamba_decode (exercises conv tail, gates, and the chunked scan across
    3 chunk boundaries — chunk size shrunk for the test)."""
    monkeypatch.setattr(SSM, "SCAN_CHUNK", 16)
    cfg = get_config("jamba-1.5-large-398b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64)
    key = jax.random.PRNGKey(0)
    p = mamba_params(key, cfg, tp=1, dtype=jnp.float32)
    rng = np.random.RandomState(1)
    B, T = 2, 48

    # dummy axis context: run under a 1-device shard_map-free trace by
    # wrapping psum axes with a single-device mesh
    from repro.compat import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("model",))
    x = jnp.asarray(rng.randn(B, T, cfg.d_model), jnp.float32)

    def full(p, x):
        return SSM.mamba_train(p, x, cfg, "model", 1)

    def steps(p, x):
        st = SSM.mamba_init_state(B, cfg, 1, jnp.float32)
        outs = []
        for t in range(T):
            y, st = SSM.mamba_decode(p, x[:, t:t + 1], st, cfg, "model", 1)
            outs.append(y)
        return jnp.concatenate(outs, axis=1)

    f1 = shard_map(full, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)
    f2 = shard_map(steps, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)
    y1, y2 = np.asarray(f1(p, x)), np.asarray(f2(p, x))
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)


def test_mlstm_train_vs_stepwise_decode(monkeypatch):
    """Chunked mLSTM == step-by-step decode recurrence (modulo the running
    max-stabilizer, which rescales numerator and denominator identically);
    chunk size shrunk so the sequence spans multiple chunk handoffs."""
    monkeypatch.setattr(SSM, "CHUNK", 16)
    cfg = get_config("xlstm-1.3b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, d_model=64, n_heads=2, head_dim=32)
    key = jax.random.PRNGKey(0)
    p = mlstm_params(key, cfg, tp=1, dtype=jnp.float32)
    rng = np.random.RandomState(2)
    B, T = 2, 48

    from repro.compat import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((1,), ("model",))
    x = jnp.asarray(rng.randn(B, T, cfg.d_model) * 0.3, jnp.float32)

    def full(p, x):
        return SSM.mlstm_train(p, x, cfg, "model", 1)

    def steps(p, x):
        st = SSM.mlstm_init_state(B, cfg, 1)
        outs = []
        for t in range(T):
            y, st = SSM.mlstm_decode(p, x[:, t:t + 1], st, cfg, "model", 1)
            outs.append(y)
        return jnp.concatenate(outs, axis=1)

    f1 = shard_map(full, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)
    f2 = shard_map(steps, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                   check_vma=False)
    y1, y2 = np.asarray(f1(p, x)), np.asarray(f2(p, x))
    np.testing.assert_allclose(y1, y2, rtol=5e-3, atol=5e-3)
