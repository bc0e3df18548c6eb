"""The ragged paged-attention kernel (Pallas interpreter on the CPU)
against the gather path it replaces on the TPU.

``paged_attention`` takes the kernel only on a TPU backend; the tests
steer that choice and leave the kernel itself in the interpreter.  Each
case builds ragged block tables through the server's allocator (striped
over the tp ranks, padded with ``FREE_BLOCK``), a tick of decode or
prefill-chunk slots and idle ones, and compares the slots' valid rows.

The pool stacks two layers and the tick reads the second.  The poisoned
pool holds NaN in the whole first layer and, in the second, in every
block that no working slot's queries can see: blocks of no request,
blocks past a slot's last new token, and blocks wholly before a window.
The kernel on it must match the gather path on the clean pool, so it
reads only those live blocks of its layer.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.models.attention import paged_attention
from repro.parallel.sharding import ParallelContext
from repro.serve.kv_cache import FREE_BLOCK, PagedKVCache

HD = 128
BLOCK = 8
MB = 8            # table width: 64 positions per slot
NB_PER_RANK = 48


def _tick(rng, *, B, C, tp, idle):
    """Tables, first positions and new-token counts of one tick: slots
    grown through the allocator in a shuffled order (so each table is
    spread over the rank stripes), the last ``idle`` slots idle."""
    kv = PagedKVCache(NB_PER_RANK * tp, BLOCK, MB, n_stripes=tp)
    pos0 = rng.integers(0, MB * BLOCK - C, size=B).astype(np.int32)
    n_new = (np.ones(B, np.int32) if C == 1
             else rng.integers(1, C + 1, size=B).astype(np.int32))
    n_new[B - idle:] = 0
    grow = [(i, L) for i in range(B - idle)
            for L in range(BLOCK, int(pos0[i] + n_new[i]) + BLOCK, BLOCK)]
    for j in rng.permutation(len(grow)):
        i, L = grow[j]
        kv.register(i)
        kv.ensure(i, min(L, int(pos0[i] + n_new[i])))
    tables = kv.tables_for([i if n_new[i] else None for i in range(B)])
    return tables, pos0, n_new


def _visible(tables, pos0, n_new, window):
    """Pool blocks some working slot's queries can see."""
    seen = set()
    for t, p, n in zip(tables, pos0, n_new):
        if not n:
            continue
        first = 0 if window is None else max(int(p) - window + 1, 0) // BLOCK
        seen.update(int(b) for b in t[first:(p + n - 1) // BLOCK + 1])
    return seen


def _run(ctx, q, pk, pv, tables, positions, n_new, window, softcap):
    return jax.jit(lambda *a: paged_attention(
        ctx, *a, layer=1, n_new=n_new, window=window, softcap_val=softcap))(
        q, pk, pv, jnp.asarray(tables), positions)


@pytest.mark.parametrize("tp", [1, 4])
@pytest.mark.parametrize("C,g,window,softcap", [
    (1, 1, None, None),
    (1, 4, 20, None),       # the window starts inside a block
    (8, 4, None, None),
    (8, 16, None, 30.0),
    (8, 1, 13, 50.0),
], ids=["decode_g1", "decode_g4_window", "chunk_g4", "chunk_g16_softcap",
        "chunk_g1_window_softcap"])
def test_kernel_matches_gather_path(monkeypatch, tp, C, g, window, softcap):
    rng = np.random.default_rng(1000 * tp + 10 * C + g)
    B, Hkv, idle = 6, 2, 2
    ctx = ParallelContext.from_mesh(make_mesh((tp,), ("model",)))
    tables, pos0, n_new = _tick(rng, B=B, C=C, tp=tp, idle=idle)
    positions = jnp.asarray(pos0[:, None] + np.arange(C)[None], jnp.int32)
    shape = (2, NB_PER_RANK * tp, BLOCK, Hkv * HD)
    pk = rng.standard_normal(shape, np.float32)
    pv = rng.standard_normal(shape, np.float32)
    q = jnp.asarray(rng.standard_normal((B, C, Hkv * g, HD), np.float32),
                    jnp.bfloat16)
    live = np.zeros(shape[:2], bool)
    live[1, list(_visible(tables, pos0, n_new, window))] = True
    poison = lambda x: jnp.asarray(np.where(live[..., None, None], x,
                                            np.nan), jnp.bfloat16)
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    nn = jnp.asarray(n_new)

    want = _run(ctx, q, bf(pk), bf(pv), tables, positions, nn, window,
                softcap)
    monkeypatch.setattr(paged_ops, "interpret_mode", lambda: False)
    got = _run(ctx, q, poison(pk), poison(pv), tables, positions, nn,
               window, softcap)
    layer = lambda x: bf(x[1].reshape(-1, BLOCK, Hkv, HD))
    ref = paged_attention_ref(q, layer(pk), layer(pv), jnp.asarray(tables),
                              positions, scale=HD ** -0.5, window=window,
                              softcap=softcap)

    valid = np.arange(C)[None] < n_new[:, None]            # [B, C]
    assert (tables[B - idle:] == FREE_BLOCK).all() and valid.sum() > 0
    got = np.asarray(got, np.float32)[valid]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want, np.float32)[valid],
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(got, np.asarray(ref)[valid],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("tp", [1, 4])
def test_block_list_keeps_live_owned_blocks_in_order(tp):
    """Each rank's list holds the blocks it owns between the slot's first
    visible and last written block, in table order; idle slots list
    nothing."""
    rng = np.random.default_rng(7 + tp)
    tables, pos0, n_new = _tick(rng, B=5, C=8, tp=tp, idle=1)
    last = np.where(n_new > 0, (pos0 + n_new - 1) // BLOCK, -1)
    first = np.maximum(pos0 - 20 + 1, 0) // BLOCK
    for d in range(tp):
        lo = d * NB_PER_RANK
        ids, lblk, cnt = map(np.asarray, paged_ops.block_list(
            jnp.asarray(tables), jnp.asarray(first), jnp.asarray(last), lo,
            NB_PER_RANK))
        for b in range(5):
            want = [(int(t) - lo, m) for m, t in enumerate(tables[b])
                    if first[b] <= m <= last[b]
                    and lo <= t < lo + NB_PER_RANK]
            assert cnt[b] == len(want)
            assert list(zip(ids[b, :cnt[b]], lblk[b, :cnt[b]])) == want
        assert cnt[-1] == 0


def test_serve_step_through_the_kernel_matches_gather_path(monkeypatch):
    """A reduced chatglm3 at tp=4: a prefill chunk, then decode ticks, on
    allocator-built tables; the step whose every layer runs the kernel
    (each reading its layer of the stacked pool) gives the gather path's
    logits and greedy tokens."""
    import repro.models.attention as attention
    from repro.configs.registry import get_arch
    from repro.models.common import split_params
    from repro.models.transformer import serve_step

    bundle = get_arch("chatglm3-6b").reduced()
    cfg = bundle.config
    params, _ = split_params(bundle.init_params(jax.random.PRNGKey(0)))
    ctx = ParallelContext.from_mesh(make_mesh((2, 4), ("data", "model")))
    prompts = [[5, 3, 7, 2, 9], [2, 9, 4], [1], []]
    B, C, block = len(prompts), 4, 4
    kv = PagedKVCache(64, block, cfg.max_seq // block, n_stripes=4)

    def generate():
        step = jax.jit(lambda *a: serve_step(ctx, params, cfg, *a))
        pool = bundle.init_paged_pool(64, block)
        toks = np.zeros((B, C), np.int32)
        n_new = np.array([min(len(p), C) for p in prompts], np.int32)
        for i, p in enumerate(prompts):
            toks[i, :n_new[i]] = p[:C]
        pos = np.zeros(B, np.int32)
        logits, out = [], []
        for t in range(5):
            for i in range(B):
                if n_new[i]:
                    kv.register(i)
                    kv.ensure(i, int(pos[i] + n_new[i]))
            tables = kv.tables_for([i if n_new[i] else None
                                    for i in range(B)])
            lg, pool = step(jnp.asarray(toks), pool, jnp.asarray(tables),
                            jnp.asarray(pos), jnp.asarray(n_new))
            lg = np.asarray(lg)[n_new > 0]
            logits.append(lg)
            out.append(lg.argmax(-1))
            pos += n_new
            toks = np.zeros((B, 1), np.int32)
            toks[n_new > 0, 0] = out[-1]
            n_new = (n_new > 0).astype(np.int32)
        kv.reset()
        return logits, out

    want_logits, want = generate()
    monkeypatch.setattr(attention, "paged_attention_kernel_supported",
                        lambda block, hd: True)
    got_logits, got = generate()
    for a, b in zip(got_logits, want_logits):
        np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)
    assert all((a == b).all() for a, b in zip(got, want))
