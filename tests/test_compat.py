"""The JAX APIs the launch code calls directly + import surface.

The tree targets one JAX (0.9.0) and calls its public API with no shims:
``jax.shard_map`` (with ``check_vma``), ``jax.set_mesh`` as a context
manager, and ``launch.mesh.make_mesh``. Each test pins the behaviour the
callers rely on; ``jax.lax.optimization_barrier`` (which has its own
differentiation rule) is pinned too, though no module calls it now. The
import sweep keeps every public module loadable.
"""
import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1]), ("x",))


def test_shard_map_shim_runs_and_reduces():
    """``jax.shard_map`` executes: split in, psum across the axis,
    replicated out."""
    mesh = _mesh1()
    a = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)

    def f(blk):
        return jax.lax.psum(blk.sum(), "x")

    fn = jax.shard_map(f, mesh=mesh, in_specs=(P("x", None),),
                       out_specs=P(), check_vma=False)
    assert float(fn(a)) == float(a.sum())


def test_shard_map_shim_replicated_operand():
    """P() in_specs replicate: every shard sees the full operand."""
    mesh = _mesh1()
    a = jnp.arange(6, dtype=jnp.float32)
    fn = jax.shard_map(lambda x: x * 2, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(a)), np.asarray(a) * 2)


def test_optimization_barrier_identity_and_grad():
    """Value passes through untouched, and the barrier is transparent to
    differentiation."""
    x = jnp.asarray([1.0, -2.0, 3.5])
    np.testing.assert_array_equal(
        np.asarray(jax.lax.optimization_barrier(x)), np.asarray(x))
    g = jax.grad(lambda v: jax.lax.optimization_barrier(v).sum())(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones(3, np.float32))


def test_set_mesh_context_form():
    """``with jax.set_mesh(mesh):`` is the context form the launchers use."""
    mesh = _mesh1()
    with jax.set_mesh(mesh):
        pass


def test_make_mesh_version_agnostic():
    """``launch.mesh.make_mesh`` builds a named mesh."""
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((jax.device_count(),), ("data",))
    assert mesh.axis_names == ("data",)


@pytest.mark.parametrize("modname", [
    "repro", "repro.launch.cluster", "repro.core.engine",
    "repro.core.measures", "repro.kernels.ops", "repro.kernels.backends",
    "repro.launch.mesh", "repro.launch.gram", "repro.launch.search",
    "repro.launch.shard_index", "repro.launch.scenarios",
    "benchmarks.check_artifacts",
])
def test_public_modules_import(modname):
    """Every public module imports under the supported JAX."""
    importlib.import_module(modname)
