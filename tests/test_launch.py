"""Launcher plumbing: weight placement at init, single-process mode,
the persistent compilation cache path."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.launch import compile_cache, distributed
from repro.launch.mesh import (init_params_on_mesh, make_host_mesh,
                               param_placements)
from repro.models.common import split_params


@pytest.mark.parametrize("arch", ["chatglm3-6b", "dbrx-132b", "zamba2-7b"])
def test_init_params_on_mesh_places_the_eager_values(arch):
    """Drawn under one jit straight into the mesh placement, the weights
    are the eager init's to the last bit, and each leaf is sharded as its
    logical spec says wherever the mesh divides the dim (zamba2's reduced
    widths do not always), as ``param_placements`` describes without
    drawing them."""
    bundle = get_arch(arch).reduced()
    ctx = make_host_mesh()
    eager, specs = split_params(bundle.init_params(jax.random.PRNGKey(0)))
    placed, placed_specs = init_params_on_mesh(bundle, ctx)
    assert placed_specs == specs
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(placed)):
        np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b),
                                        maxulp=1)
    described, described_specs = param_placements(bundle, ctx)
    assert described_specs == specs
    for s, b in zip(jax.tree.leaves(described), jax.tree.leaves(placed)):
        assert (s.shape, s.dtype, s.sharding) == (b.shape, b.dtype,
                                                  b.sharding)
    table = placed["embed"]["table"] if "embed" in placed else None
    if table is not None:
        assert table.sharding.spec == ctx.spec("tp", "fsdp")


def test_no_coordinator_means_single_process(monkeypatch):
    """Without a coordinator nothing is initialized: no cluster
    auto-detection, which can hang where no metadata server answers."""
    for var in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)

    def refuse(*a, **k):
        raise AssertionError("jax.distributed.initialize was called")

    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    assert distributed.initialize_distributed() is False


def test_coordinator_without_world_is_an_error(monkeypatch):
    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.delenv("JAX_NUM_PROCESSES", raising=False)
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    with pytest.raises(ValueError, match="num_processes"):
        distributed.initialize_distributed("localhost:1234")


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The environment's cache directory wins and nothing is set in
    code; otherwise the cache sits at one fixed path in the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent
                / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
