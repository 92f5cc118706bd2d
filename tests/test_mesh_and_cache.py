"""The one mesh constructor (``repro.launch.mesh``), the compile-cache
placement (``repro.launch.cache``) and the sharded state the launchers
create."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import AxisType

from repro.launch.cache import CHECKOUT, use_compile_cache
from repro.launch.mesh import make_mesh

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("shape,axes", [((1,), ("d",)),
                                        ((1, 1), ("data", "model"))])
def test_make_mesh_axes_are_auto(shape, axes):
    mesh = make_mesh(shape, axes)
    assert mesh.axis_names == axes
    assert mesh.axis_types == (AxisType.Auto,) * len(axes)


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_checkout(cache_dir_restored, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert use_compile_cache() == want                  # idempotent
    assert (Path(CHECKOUT) / "src" / "repro").is_dir()


def test_compile_cache_env_wins(cache_dir_restored, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir is None


_SHARDED_CODE = r"""
import dataclasses, numpy as np, jax
from jax.sharding import AxisType
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.train import init_sharded_state
from repro.train.step import _ns, make_train_step

devs = jax.devices()
mesh = make_mesh((4,), ("nodes",), devices=devs[::-1])
assert [d.id for d in mesh.devices.flat] == [3, 2, 1, 0]
assert mesh.axis_types == (AxisType.Auto,)

cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                          tie_embeddings=False)
mesh = make_mesh((4, 1), ("data", "model"))
_, specs = make_train_step(cfg, mesh, sync="sparse", dp_degrees=None)
params, opt = init_sharded_state(cfg, mesh)
for x in jax.tree.leaves((params, opt)):
    assert len(x.sharding.device_set) == 4, x.sharding
tok = jax.device_put(np.zeros((8, 16), np.int32),
                     _ns(mesh, specs["batch"])["tokens"])
assert sorted(s.data.shape for s in tok.addressable_shards) == [(2, 16)] * 4
print("SHARDED_OK")
"""


def test_state_and_batches_created_sharded():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _SHARDED_CODE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "SHARDED_OK" in r.stdout, r.stderr[-3000:]
