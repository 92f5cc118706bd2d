"""End-to-end behaviour: training converges; serving is self-consistent;
the public SparseAllreduce API round-trips through both backends."""
import numpy as np
import pytest

from repro.core import SparseAllreduce
from repro.core.simulator import dense_oracle
from repro.launch.mesh import make_mesh


def test_train_loss_decreases():
    """Deterministic memorization check: repeated batch, loss must drop
    sharply (fresh-stream convergence is exercised by the launcher test)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.optim.adamw import AdamW
    from repro.train.step import make_train_step
    cfg = get_config("qwen1.5-0.5b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    step, _ = make_train_step(cfg, mesh, opt=AdamW(lr=1e-3))
    params = T.init_params(cfg, 1, seed=0)
    opt = AdamW().init(params)
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (4, 64)), jnp.int32),
             "labels": jnp.asarray(rng.randint(0, cfg.vocab, (4, 64)), jnp.int32)}
    losses = []
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 1.0, losses


def test_train_launcher_runs():
    from repro.launch.train import main as train_main
    loss = train_main(["--arch", "qwen1.5-0.5b", "--reduced",
                       "--steps", "8", "--batch", "4", "--seq", "64"])
    assert np.isfinite(loss) and loss < 8.0


def test_train_sparse_sync_untied():
    from repro.launch.train import main as train_main
    loss = train_main(["--arch", "qwen1.5-0.5b", "--reduced", "--untied",
                       "--sync", "sparse", "--steps", "6", "--batch", "4",
                       "--seq", "64"])
    assert np.isfinite(loss)


def test_serve_generates():
    from repro.launch.serve import main as serve_main
    gen = serve_main(["--arch", "qwen1.5-0.5b", "--reduced",
                      "--requests", "2", "--prompt-len", "16", "--gen", "6"])
    assert gen.shape == (2, 6)
    assert gen.dtype == np.int32


def test_api_device_backend_matches_sim():
    """Same indices/values through backend='sim' and backend='device'
    (device path runs on 1 CPU device with a 1-node mesh fallback? no —
    8 logical nodes need 8 devices; use the sim-vs-device equivalence via
    the planned path on a single-device 1-node instance)."""
    rng = np.random.RandomState(0)
    M, R = 1, 500
    out_idx = [rng.randint(0, R, 40).astype(np.uint32)]
    out_val = [rng.randn(40)]
    in_idx = [rng.choice(R, 30, replace=False).astype(np.uint32)]
    for backend in ("sim", "device"):
        ar = SparseAllreduce(M, (), backend=backend, seed=3)
        ar.config(out_idx, in_idx)
        got = ar.reduce(out_val)
        want = dense_oracle(out_idx, out_val, in_idx, ar.perm)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)


def test_api_device_reduce_reuses_staging():
    """Repeated same-config reduces must reuse the host staging buffer
    (no per-call np.zeros + per-node copy loop) and stay correct when the
    values change between calls — including with value_width > 1."""
    rng = np.random.RandomState(1)
    M, R, W = 1, 400, 3
    out_idx = [rng.randint(0, R, 50).astype(np.uint32)]
    in_idx = [rng.choice(R, 25, replace=False).astype(np.uint32)]
    ar = SparseAllreduce(M, (), backend="device", seed=5, value_width=W)
    ar.config(out_idx, in_idx)
    assert ar._staging is None                  # built lazily on first call
    for it in range(3):
        out_val = [rng.randn(50, W)]
        got = ar.reduce(out_val)
        want = dense_oracle(out_idx, out_val, in_idx, ar.perm, width=W)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
        if it == 0:
            staging = ar._staging
        else:                                   # same buffer, not re-alloc'd
            assert ar._staging is staging
    with pytest.raises(ValueError):             # wrong total length
        ar.reduce([np.zeros((49, W))])


def test_whisper_end_to_end_serve():
    from repro.launch.serve import main as serve_main
    gen = serve_main(["--arch", "whisper-base", "--reduced",
                      "--requests", "2", "--prompt-len", "8", "--gen", "4"])
    assert gen.shape == (2, 4)
