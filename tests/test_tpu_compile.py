"""The Pallas kernels compile for the TPU v5e (a described ``v5e:2x2``
topology; nothing runs).

Interpret-mode tests cannot see what the chip's compiler refuses — a 1-D
block that splits the 1024-element tiling of a 1-D array, a gather it has
no lowering for, more fast memory than a kernel may use.  Each test here
compiles one kernel (or the whole merge pipeline) at the sizes the
smoke run uses and checks that the Mosaic kernel is in the program
(``tpu_custom_call``), i.e. the compiled path and not the interpreter.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.onehot_scatter import (banded_onehot_scatter_add,
                                          onehot_scatter_add)
from repro.kernels.rank_merge import rank_counts

# (C, W): a narrow union at full capacity, and a wide one.  The fused
# scatter is O(C^2 W), so C=65,536 is compiled only at W=1.
CASES = [(65536, 1), (4096, 1024)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("scaled", [False, True])
@pytest.mark.parametrize("banded", [False, True])
@pytest.mark.parametrize("c,w", CASES)
def test_onehot_scatter_compiles(one_chip, c, w, banded, scaled):
    pos = _spec(one_chip, (c,), jnp.int32)
    # the scaled form is the wire decode: int8 values, f32 scale per row
    val = _spec(one_chip, (c, w), jnp.int8 if scaled else jnp.float32)
    scale = _spec(one_chip, (c,), jnp.float32) if scaled else None
    if banded:
        def fn(p, v, s=None):
            return banded_onehot_scatter_add(p, v, c, band=4, scale=s,
                                             interpret=False)
    else:
        def fn(p, v, s=None):
            return onehot_scatter_add(p, v, c, scale=s, interpret=False)
    args = (pos, val) + ((scale,) if scaled else ())
    assert "tpu_custom_call" in _compiled_text(fn, *args)


@pytest.mark.parametrize("strict", [True, False])
@pytest.mark.parametrize("banded", [False, True])
def test_rank_counts_compiles(one_chip, banded, strict):
    a = _spec(one_chip, (65536,), jnp.uint32)

    def fn(x, y):
        return rank_counts(x, y, strict=strict, banded=banded,
                           interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, a, a)


@pytest.mark.parametrize("mode", ops.MERGE_KERNEL_MODES)
def test_merge_pipeline_compiles(one_chip, mode, monkeypatch):
    """The whole k-way merge (rank planes + compact + scatter) as the
    butterfly calls it; the test steers ``interpret_mode`` to the chip's
    answer, since this process's backend is the CPU."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    k, c = 4, 65536
    idx = _spec(one_chip, (k, c // k), jnp.uint32)
    val = _spec(one_chip, (k, c // k), jnp.float32)
    text = _compiled_text(
        lambda i, v: ops.merge_sorted_runs(i, v, c, mode=mode), idx, val)
    # k*(k-1) rank planes and one scatter-add
    assert text.count("tpu_custom_call") >= k * (k - 1) + 1
