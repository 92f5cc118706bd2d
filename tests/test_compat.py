"""repro.compat: the re-exported JAX symbols are the installed ones and
behave as the repo uses them.

The layering rule (these symbols are imported only through compat.py)
lives in the lint engine — rules RA101/RA102 in ``repro.analysis.rules``,
enforced repo-wide by tests/test_analysis.py.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.launch.mesh import make_mesh


def test_shard_map_is_installed_jax_shard_map():
    assert compat.shard_map is jax.shard_map
    assert "check_vma" in inspect.signature(compat.shard_map).parameters


@pytest.mark.parametrize("check_vma", [True, False, None])
def test_shard_map_runs_with_check_vma(check_vma):
    mesh = make_mesh((1,), ("d",))
    kw = {} if check_vma is None else {"check_vma": check_vma}
    f = compat.shard_map(lambda x: x * 2.0, mesh=mesh, in_specs=P("d"),
                         out_specs=P("d"), **kw)
    np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                  np.arange(4.0) * 2.0)


def test_compiler_params_usable_on_installed_jax():
    cp = compat.CompilerParams(dimension_semantics=("parallel",))
    assert cp.dimension_semantics == ("parallel",)


def test_prefetch_grid_spec_usable_on_installed_jax():
    gs = compat.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=(2,))
    assert gs.grid == (2,)
