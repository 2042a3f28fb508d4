"""JAX runtime helpers: compile-cache placement and manual-axis typing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Restore the cache-dir option before anything else compiles."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_env_and_sets_nothing(monkeypatch, tmp_path,
                                                 cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compat.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compat.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_make_mesh_axis_is_auto():
    mesh = compat.make_mesh(jax.devices()[:1], "variants")
    assert mesh.axis_names == ("variants",)
    assert mesh.axis_types == (AxisType.Auto,)


def test_vary_like_and_match_vma_outside_shard_map_are_identity():
    tree = {"a": jnp.ones(3)}
    assert compat.vary_like(tree, jnp.ones(2)) is tree
    x, y = jnp.ones(2), jnp.zeros(())
    assert compat.match_vma(x, y) == (x, y)


def test_vary_like_types_a_scan_carry_inside_shard_map():
    """A carry seeded from a constant must vary like the per-shard input,
    or scan refuses the body (carry in and out types differ)."""
    mesh = compat.make_mesh(jax.devices()[:1], "v")

    def per_shard(x):
        def body(c, xi):
            return c + xi, None
        init = compat.vary_like(jnp.zeros(x.shape[1:]), x)
        assert jax.typeof(init).vma == {"v"}
        y, _ = compat.match_vma(jnp.float32(2.0), x)
        assert jax.typeof(y).vma == {"v"}
        return lax.scan(body, init, x)[0][None]

    f = jax.shard_map(per_shard, mesh=mesh, in_specs=P("v"),
                      out_specs=P("v"))
    x = jnp.arange(6.0).reshape(3, 2)
    np.testing.assert_array_equal(np.asarray(f(x))[0], [6.0, 9.0])
