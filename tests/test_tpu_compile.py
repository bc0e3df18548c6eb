"""Compile the fused kernels for a described TPU v5e 2x2 host.

Nothing runs: each test lowers one kernel (or one decode step) at
published widths and compiles it with the chip's own compiler, which
refuses what the interpreter lets through (tiling, VMEM budget, remote
DMA addressing, the barrier/``collective_id`` pairing).  A compiled
program holding ``tpu_custom_call`` proves the Pallas kernel is in it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports every
test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map

N_DEV = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices).reshape(1, N_DEV), ("data", "model"))


def _struct(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _assert_kernel_compiles(fn, *args, name):
    """Compiles, holds the Pallas call, and names its instruction after
    the kernel's family (the name a trace shows for its op)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}\.\d+ = ", text)
    return text


def _ring_pos():
    return lax.axis_index("model")


# chatglm3-6b at tp=4: the attention out-projection and the FFN-down
@pytest.mark.parametrize("k,n", [(4096, 4096), (13696, 4096)],
                         ids=["w_o", "ffn_down"])
@pytest.mark.parametrize("rows", [4, 32], ids=["decode", "prefill_chunk"])
def test_gemv_allreduce_kernel_compiles(mesh, k, n, rows):
    from repro.kernels.fused_gemv_allreduce.kernel import (
        fused_matmul_allreduce_pallas)

    def fn(x, w):
        return shard_map(
            lambda xl, wl: fused_matmul_allreduce_pallas(
                xl, wl, _ring_pos(), n_dev=N_DEV, axis_name="model",
                interpret=False),
            mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=P(None, None))(x, w)

    _assert_kernel_compiles(
        fn, _struct(mesh, (rows, k), jnp.bfloat16, P(None, "model")),
        _struct(mesh, (k, n), jnp.bfloat16, P("model", None)),
        name="fused_gemv_allreduce")


def test_dispatch_a2a_kernel_compiles(mesh):
    """dbrx-132b widths: d_model 6144, 16 experts = 4 per chip."""
    from repro.kernels.fused_dispatch_a2a.kernel import (
        fused_dispatch_a2a_pallas)

    b, e_loc, cap, d = 1, 4, 16, 6144

    def fn(x):
        return shard_map(
            lambda xl: fused_dispatch_a2a_pallas(
                xl[0], _ring_pos(), jnp.int32(0), n_dev=N_DEV,
                axis_name="model", interpret=False)[None],
            mesh=mesh, in_specs=P("model"), out_specs=P("model"))(x)

    _assert_kernel_compiles(
        fn, _struct(mesh, (N_DEV, N_DEV, b, e_loc, cap, d), jnp.bfloat16,
                    P("model")),
        name="fused_dispatch_a2a")


def test_gemm_a2a_kernel_compiles(mesh):
    """deepseek-v3-671b expert widths (d_model 7168, expert d_ff 2048),
    one expert per chip, weights streamed in 256- and 128-row panels."""
    from repro.kernels.fused_gemm_a2a.kernel import fused_gemm_a2a_pallas

    b, e_loc, cap, d, f = 1, 1, 16, 7168, 2048

    def fn(x, wu, wg, wd):
        def local(xl, u, g, dn):
            return fused_gemm_a2a_pallas(
                xl[0], u, g, dn, _ring_pos(), jnp.int32(0), n_dev=N_DEV,
                axis_name="model", act=jax.nn.silu, interpret=False,
                tile_k=256, tile_f=128)[None]

        return shard_map(local, mesh=mesh, in_specs=(P("model"),) * 4,
                         out_specs=P("model"))(x, wu, wg, wd)

    w = lambda *shape: _struct(mesh, shape, jnp.bfloat16, P("model"))
    _assert_kernel_compiles(
        fn, w(N_DEV, N_DEV, b, e_loc, cap, d), w(N_DEV * e_loc, d, f),
        w(N_DEV * e_loc, d, f), w(N_DEV * e_loc, f, d),
        name="fused_gemm_a2a")


def test_embedding_a2a_kernel_compiles(mesh):
    """DLRM widths (embedding dim 92, pooling 70, f32), 4 tables per
    chip, 4096-row tables, 32 samples."""
    from repro.kernels.fused_embedding_a2a.kernel import (
        fused_embedding_a2a_pallas)

    t_loc, vocab, d, pooling, batch = 4, 4096, 92, 70, 32

    def fn(idx, tables):
        return shard_map(
            lambda il, tl: fused_embedding_a2a_pallas(
                tl, il, _ring_pos(), n_dev=N_DEV, L=pooling,
                axis_name="model", interpret=False),
            mesh=mesh, in_specs=(P(None, "model", None),
                                 P("model", None, None)),
            out_specs=P("model", None, None))(idx, tables)

    _assert_kernel_compiles(
        fn, _struct(mesh, (batch, N_DEV * t_loc, pooling), jnp.int32,
                    P(None, "model", None)),
        _struct(mesh, (N_DEV * t_loc, vocab, d), jnp.float32,
                P("model", None, None)),
        name="fused_embedding_a2a")


# the benchmark's cells: chatglm3-6b on one chip (32 slots, 2,048 pool
# blocks) and phi3-medium-14b at tp=4 (16 slots, 1,024 blocks, window
# 2047), 16-token blocks, 256-block tables, the pool of every layer
@pytest.mark.parametrize("cell", ["glm6b", "phi3m_tp4"])
@pytest.mark.parametrize("chunk", [1, 8], ids=["decode", "prefill_chunk"])
def test_paged_attention_kernel_compiles(topo, mesh, monkeypatch, cell,
                                         chunk):
    import repro.kernels
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.models.attention import paged_attention
    from repro.parallel.sharding import ParallelContext

    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(paged_ops, "interpret_mode", lambda: False)
    layers, b, hq, hkv, nb, window, tp = {
        "glm6b": (28, 32, 32, 2, 2048, None, 1),
        "phi3m_tp4": (40, 16, 40, 10, 1024, 2047, N_DEV)}[cell]
    on = Mesh(np.array(topo.devices[:tp]).reshape(1, tp), ("data", "model"))
    ctx = ParallelContext.from_mesh(on)
    rep = lambda *shape: _struct(on, shape, jnp.int32, P())
    pool = _struct(on, (layers, nb, 16, hkv * 128), jnp.bfloat16,
                   P(None, "model"))

    def fn(q, k, v, tables, positions, n_new, layer):
        return paged_attention(ctx, q, k, v, tables, positions, layer=layer,
                               n_new=n_new, window=window)

    _assert_kernel_compiles(
        fn, _struct(on, (b, chunk, hq, 128), jnp.bfloat16, P()), pool, pool,
        rep(b, 256), rep(b, chunk), rep(b), rep(), name="paged_attention")


def test_chatglm3_kernel_decode_step_compiles(mesh, monkeypatch):
    """The whole chatglm3-6b paged decode step at full width, tp=4,
    ``--fusion kernel``: the kernel is chosen (not the XLA fallback)."""
    import repro.kernels
    from repro.configs.registry import get_arch
    from repro.kernels.fused_gemv_allreduce import ops as gemv_ops
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.models.common import split_params
    from repro.parallel.sharding import FusionConfig, ParallelContext

    # the code asks the default backend (the CPU here) whether to run the
    # interpreter; this program is for the described chip
    monkeypatch.setattr(repro.kernels, "interpret_mode", lambda: False)
    monkeypatch.setattr(gemv_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(paged_ops, "interpret_mode", lambda: False)

    bundle = get_arch("chatglm3-6b")
    ctx = ParallelContext.from_mesh(mesh, fusion=FusionConfig(mode="kernel"))
    struct, specs = split_params(
        jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0)))
    is_spec = lambda x: isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)
    params = jax.tree.map(
        lambda s, a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=ctx.sharding(*s)),
        specs, struct, is_leaf=is_spec)
    cfg = bundle.config
    pool = jax.eval_shape(lambda: bundle.init_paged_pool(512, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = _assert_kernel_compiles(
        bundle.serve_step_fn(ctx), params, i32(4, 1), pool,
        i32(4, -(-cfg.max_seq // 16)), i32(4), i32(4),
        name="fused_gemv_allreduce")
    # the served step's named scopes reach the chip compiler's metadata:
    # the GEMV+AllReduce kernel under the MLP, the paged-attention kernel
    # under paged attention
    assert re.search(r'op_name="[^"]*/mlp/[^"]*/fused_gemv_allreduce/'
                     r'[^"]*pallas_call"', text)
    assert re.search(r'op_name="[^"]*/attn\.paged/[^"]*paged_attention/'
                     r'[^"]*pallas_call"', text)
