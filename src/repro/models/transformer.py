"""Unified decoder-only LM covering the dense/MoE/audio/VLM architectures.

One config describes layer structure (GQA or MLA attention, dense or MoE
FFN, local/global window alternation, RoPE flavor, softcaps); layers are
scanned in homogeneous *groups* (a group = one period of the layer
pattern) so the lowered HLO stays compact for the 40-95 layer configs.

All activations run sequence-sharded over tp (train/prefill) with the
fused operators from repro.core at every collective site; decode runs
with replicated single-token activations, sequence-sharded KV caches and
the fused GEMV+AllReduce FFN (the paper's flagship op).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.loss import sharded_cross_entropy
from repro.models import moe as moe_mod
from repro.models import mla as mla_mod
from repro.models.attention import (broadcast_pos, cache_update,
                                    context_attention, decode_attention,
                                    paged_attention, paged_cache_update)
from repro.models.common import Param, dense_init, is_param, key_iter
from repro.models.layers import embedding_init, embedding_lookup, mlp_apply, mlp_init, rms_norm, rms_norm_init
from repro.models.rope import apply_mrope, apply_rope, apply_rope_2d
from repro.parallel.sharding import ParallelContext


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    act: str = "silu"
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_style: str = "full"           # full | 2d | mrope
    mrope_sections: tuple = (16, 24, 24)
    logit_softcap: float | None = None
    attn_softcap: float | None = None
    window: int | None = None          # sliding window for local layers
    local_global_period: int = 0       # gemma2: 2 -> [local, global] pattern
    query_scale: float | None = None
    embed_scale: bool = False          # gemma: x *= sqrt(d_model)
    post_norms: bool = False           # gemma2 post-attn/ffn norms
    norm_plus_one: bool = False        # gemma (1+w) RMSNorm
    attn_type: str = "gqa"             # gqa | mla
    mla: mla_mod.MLAConfig | None = None
    moe: moe_mod.MoEConfig | None = None
    dense_prefix: int = 0              # deepseek-v3: first k layers dense
    frontend: str | None = None        # None | audio | vision
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    max_seq: int = 4096                # KV-cache length for decode
    remat: bool = True
    sub_quadratic: bool = False        # True for SSM/hybrid (long_500k ok)

    @property
    def hd(self):
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pattern_len(self):
        return self.local_global_period or 1

    def layer_window(self, idx_in_pattern: int):
        if not self.local_global_period:
            return self.window if self.window else None
        # gemma2 style: even layers local, odd layers global
        return self.window if idx_in_pattern % 2 == 0 else None

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _layer_init(key, cfg: TransformerConfig, window):
    ks = key_iter(key)
    D = cfg.d_model
    p: dict[str, Any] = {"ln1": rms_norm_init(D, jnp.float32, zero=cfg.norm_plus_one),
                         "ln2": rms_norm_init(D, jnp.float32, zero=cfg.norm_plus_one)}
    if cfg.post_norms:
        p["post_ln1"] = rms_norm_init(D, jnp.float32, zero=cfg.norm_plus_one)
        p["post_ln2"] = rms_norm_init(D, jnp.float32, zero=cfg.norm_plus_one)
    if cfg.attn_type == "mla":
        p["attn"] = mla_mod.mla_init(next(ks), cfg.mla, cfg.pdtype)
    else:
        qkv = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.hd
        p["attn"] = {
            "w_qkv": dense_init(next(ks), (D, qkv), ("fsdp", None), cfg.pdtype),
            "w_o": dense_init(next(ks), (cfg.n_heads * cfg.hd, D), (None, "fsdp"), cfg.pdtype),
        }
    return p


def _ffn_init(key, cfg: TransformerConfig, dense: bool):
    if cfg.moe is not None and not dense:
        return moe_mod.moe_init(key, cfg.moe, cfg.pdtype)
    return mlp_init(key, cfg.d_model, cfg.d_ff, cfg.pdtype, act=cfg.act)


def _group_init(key, cfg: TransformerConfig, dense: bool):
    """One scan group = pattern_len consecutive layers."""
    ks = key_iter(key)
    group = []
    for i in range(cfg.pattern_len):
        lp = _layer_init(next(ks), cfg, cfg.layer_window(i))
        lp["ffn"] = _ffn_init(next(ks), cfg, dense)
        group.append(lp)
    return {f"l{i}": g for i, g in enumerate(group)}


def stacked_init(key, n: int, init_fn):
    """vmap an init over n layer keys; Param specs gain a leading None."""
    keys = jax.random.split(key, n)
    proto = init_fn(keys[0])
    flat_proto, treedef = jax.tree.flatten(proto, is_leaf=is_param)

    def values_fn(k):
        t = init_fn(k)
        return [p.value for p in jax.tree.leaves(t, is_leaf=is_param)]

    vals = jax.vmap(values_fn)(keys)
    out = [Param(v, (None,) + tuple(p.spec)) for v, p in zip(vals, flat_proto)]
    return jax.tree.unflatten(treedef, out)


def transformer_init(key, cfg: TransformerConfig):
    ks = key_iter(key)
    n_scan = (cfg.n_layers - cfg.dense_prefix) // cfg.pattern_len
    assert (cfg.n_layers - cfg.dense_prefix) % cfg.pattern_len == 0, cfg.name
    params: dict[str, Any] = {
        "embed": embedding_init(next(ks), cfg.vocab, cfg.d_model, cfg.pdtype),
        "final_norm": rms_norm_init(cfg.d_model, jnp.float32, zero=cfg.norm_plus_one),
        "layers": stacked_init(next(ks), n_scan, lambda k: _group_init(k, cfg, dense=False)),
    }
    if cfg.dense_prefix:
        params["prefix"] = [
            {"l0": {**_layer_init(next(ks), cfg, cfg.layer_window(0)),
                    "ffn": _ffn_init(next(ks), cfg, dense=True)}}
            for _ in range(cfg.dense_prefix)
        ]
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------
def _apply_rope_any(cfg, x, positions):
    if cfg.rope_style == "2d":
        return apply_rope_2d(x, positions, theta=cfg.rope_theta)
    if cfg.rope_style == "mrope":
        return apply_mrope(x, positions, theta=cfg.rope_theta,
                           sections=cfg.mrope_sections)
    return apply_rope(x, positions, theta=cfg.rope_theta)


def _attn_train(ctx, cfg: TransformerConfig, lp, x, positions, window,
                collect_kv=False):
    B, S, D = x.shape
    h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    if cfg.attn_type == "mla":
        out, latents = mla_mod.mla_context_attention(ctx, lp["attn"], cfg.mla, h)
        kv = {"c": latents[0], "kr": latents[1]} if collect_kv else None
        return out, kv
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    qkv = h @ lp["attn"]["w_qkv"]
    q, k, v = jnp.split(qkv, [Hq * hd, (Hq + Hkv) * hd], axis=-1)
    q = q.reshape(B, S, Hq, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    q = _apply_rope_any(cfg, q, positions)
    k = _apply_rope_any(cfg, k, positions)
    o = context_attention(ctx, q, k, v, causal=True, window=window,
                          scale=cfg.query_scale, softcap_val=cfg.attn_softcap)
    kv = {"k": k, "v": v} if collect_kv else None
    return o.reshape(B, S, Hq * hd) @ lp["attn"]["w_o"], kv


def _layer_train(ctx, cfg: TransformerConfig, lp, x, positions, window,
                 collect_kv=False):
    a, kv = _attn_train(ctx, cfg, lp, x, positions, window, collect_kv)
    if cfg.post_norms:
        a = rms_norm(a, lp["post_ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    if cfg.moe is not None and "router" in lp["ffn"]:
        f = moe_mod.moe_apply(ctx, lp["ffn"], h, cfg.moe)
    else:
        f = mlp_apply(ctx, lp["ffn"], h, act=cfg.act, seq_sharded=True)
    if cfg.post_norms:
        f = rms_norm(f, lp["post_ln2"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    return x + f, kv


def _embed_inputs(ctx, params, cfg: TransformerConfig, batch, *, seq_shard):
    """tokens and/or stub-frontend embeddings -> x [B, S, D]."""
    tokens = batch["tokens"]
    scale = cfg.d_model ** 0.5 if cfg.embed_scale else None
    x = embedding_lookup(ctx, params["embed"], tokens,
                         seq_shard=seq_shard, scale=scale)
    x = x.astype(cfg.cdtype)
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        is_v = batch["vision_mask"]  # [S] bool
        x = jnp.where(is_v[None, :, None], batch["vision_embeds"].astype(cfg.cdtype), x)
    if cfg.frontend == "audio" and "frame_embeds" in batch:
        x = x + batch["frame_embeds"].astype(cfg.cdtype)
    return x


def _positions_for(cfg, batch, S):
    if cfg.rope_style == "mrope":
        return batch["positions_thw"]  # [3, B, S]
    return jnp.arange(S)[None, :]


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def train_forward(ctx: ParallelContext, params, cfg: TransformerConfig, batch):
    """batch: {tokens [B,S], labels [B,S], (frontend extras)} -> scalar loss."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_inputs(ctx, params, cfg, batch, seq_shard=True)
    positions = _positions_for(cfg, batch, S)

    for lp in params.get("prefix", []):
        x, _ = _layer_train(ctx, cfg, lp["l0"], x, positions, cfg.layer_window(0))

    def group_body(carry, group_params):
        h = carry
        for i in range(cfg.pattern_len):
            h, _ = _layer_train(ctx, cfg, group_params[f"l{i}"], h, positions,
                                cfg.layer_window(i))
        return h, ()

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(group_body, prevent_cse=False)
    x, _ = lax.scan(body, x, params["layers"])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    return sharded_cross_entropy(ctx, x, params["embed"]["table"],
                                 batch["labels"], logit_softcap=cfg.logit_softcap)


def prefill_forward(ctx: ParallelContext, params, cfg: TransformerConfig, batch):
    """Inference prefill: forward over the prompt, returning last-position
    logits [B, 1, V] and the per-layer KV/latent cache (seq dim = S)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_inputs(ctx, params, cfg, batch, seq_shard=True)
    positions = _positions_for(cfg, batch, S)

    prefix_kv = []
    for lp in params.get("prefix", []):
        x, kv = _layer_train(ctx, cfg, lp["l0"], x, positions,
                             cfg.layer_window(0), collect_kv=True)
        prefix_kv.append(kv)

    def group_body(carry, group_params):
        h = carry
        kvs = []
        for i in range(cfg.pattern_len):
            h, kv = _layer_train(ctx, cfg, group_params[f"l{i}"], h, positions,
                                 cfg.layer_window(i), collect_kv=True)
            kvs.append(kv)
        return h, jax.tree.map(lambda *xs: jnp.stack(xs), *kvs)

    x, scan_kv = lax.scan(group_body, x, params["layers"])
    n_scan_layers = cfg.n_layers - cfg.dense_prefix
    cache = {"scan": jax.tree.map(
        lambda c: c.reshape((n_scan_layers,) + c.shape[2:]), scan_kv)}
    if prefix_kv:
        cache["prefix"] = jax.tree.map(lambda *xs: jnp.stack(xs), *prefix_kv)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    x_last = jax.lax.with_sharding_constraint(
        x[:, S - 1:], ctx.sharding("batch", None, None))
    logits = _lm_logits(ctx, params, cfg, x_last)
    return logits, cache


# --- decode --------------------------------------------------------------
def init_cache(cfg: TransformerConfig, batch_size: int):
    """Zeroed decode caches (values only; shardings via cache_specs)."""
    S = cfg.max_seq
    n_scan = (cfg.n_layers - cfg.dense_prefix) // cfg.pattern_len

    def one(n):
        if cfg.attn_type == "mla":
            return {"c": jnp.zeros((n, batch_size, S, cfg.mla.kv_lora_rank), cfg.cdtype),
                    "kr": jnp.zeros((n, batch_size, S, cfg.mla.qk_rope_dim), cfg.cdtype)}
        return {"k": jnp.zeros((n, batch_size, S, cfg.n_kv_heads, cfg.hd), cfg.cdtype),
                "v": jnp.zeros((n, batch_size, S, cfg.n_kv_heads, cfg.hd), cfg.cdtype)}

    cache = {"scan": one(n_scan * cfg.pattern_len)}
    if cfg.dense_prefix:
        cache["prefix"] = one(cfg.dense_prefix)
    return cache


def cache_logical_specs(cfg: TransformerConfig, cache):
    """Logical sharding specs for a cache pytree: [L, B, S(seq), ...]."""
    def spec(x):
        return (None, "batch", "seq") + (None,) * (x.ndim - 3)
    return jax.tree.map(spec, cache)


def _attn_decode(ctx, cfg: TransformerConfig, lp, x, layer_cache, pos, window):
    """One decode-attention step.  ``pos`` is the per-slot position vector
    [B] — each batch slot applies RoPE, writes its KV, and masks its
    attention at its *own* length (continuous batching admits requests
    into freed slots at position 0 while neighbors keep counting)."""
    B = x.shape[0]
    h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    if cfg.attn_type == "mla":
        c_new, kr_new = mla_mod.mla_latents_for_cache(
            lp["attn"], cfg.mla, h, pos[:, None])
        cc = cache_update(ctx, layer_cache["c"], c_new, pos)
        kr = cache_update(ctx, layer_cache["kr"], kr_new, pos)
        out = mla_mod.mla_decode_attention(ctx, lp["attn"], cfg.mla, h, cc, kr, pos)
        return out, {"c": cc, "kr": kr}
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    qkv = h @ lp["attn"]["w_qkv"]
    q, k, v = jnp.split(qkv, [Hq * hd, (Hq + Hkv) * hd], axis=-1)
    q = q.reshape(B, 1, Hq, hd)
    k = k.reshape(B, 1, Hkv, hd)
    v = v.reshape(B, 1, Hkv, hd)
    positions = pos[:, None]                         # [B, 1] per-slot
    if cfg.rope_style == "mrope":  # text-phase decode: three equal streams
        positions = jnp.broadcast_to(positions, (3, B, 1))
    q = _apply_rope_any(cfg, q, positions)
    k = _apply_rope_any(cfg, k, positions)
    kc = cache_update(ctx, layer_cache["k"], k, pos)
    vc = cache_update(ctx, layer_cache["v"], v, pos)
    o = decode_attention(ctx, q, kc, vc, pos, window=window,
                         scale=cfg.query_scale, softcap_val=cfg.attn_softcap)
    out = o.reshape(B, 1, Hq * hd) @ lp["attn"]["w_o"]
    return out, {"k": kc, "v": vc}


def _layer_decode(ctx, cfg, lp, x, layer_cache, pos, window):
    a, new_cache = _attn_decode(ctx, cfg, lp, x, layer_cache, pos, window)
    if cfg.post_norms:
        a = rms_norm(a, lp["post_ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    if cfg.moe is not None and "router" in lp["ffn"]:
        f = moe_mod.moe_apply(ctx, lp["ffn"], h, cfg.moe)
    else:
        f = mlp_apply(ctx, lp["ffn"], h, act=cfg.act, seq_sharded=False)
    if cfg.post_norms:
        f = rms_norm(f, lp["post_ln2"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    return x + f, new_cache


def decode_step(ctx: ParallelContext, params, cfg: TransformerConfig,
                tokens, cache, pos):
    """One decode step.  tokens: [B, 1]; pos: [B] int32 (0-based position
    of each slot's new token; a scalar broadcasts — every slot at the
    same offset, the pre-continuous-batching behavior).  Returns
    (logits [B, 1, V], updated cache)."""
    B = tokens.shape[0]
    pos = broadcast_pos(pos, B)
    scale = cfg.d_model ** 0.5 if cfg.embed_scale else None
    x = embedding_lookup(ctx, params["embed"], tokens, seq_shard=False,
                         scale=scale).astype(cfg.cdtype)

    new_prefix = []
    for i, lp in enumerate(params.get("prefix", [])):
        lc = jax.tree.map(lambda c: c[i], cache["prefix"])
        x, nc = _layer_decode(ctx, cfg, lp["l0"], x, lc, pos, cfg.layer_window(0))
        new_prefix.append(nc)

    # cache threads through the scan as a *carry* with in-place
    # dynamic-update-slice writes, so a donated cache buffer aliases all
    # the way through the loop (no xs/ys double-buffering).
    n_scan_layers = (cfg.n_layers - cfg.dense_prefix)

    def group_body(carry, group_params):
        h, scan_cache, li = carry
        for i in range(cfg.pattern_len):
            lc = jax.tree.map(
                lambda c: lax.dynamic_index_in_dim(c, li + i, 0, keepdims=False),
                scan_cache)
            h, nc = _layer_decode(ctx, cfg, group_params[f"l{i}"], h, lc, pos,
                                  cfg.layer_window(i))
            scan_cache = jax.tree.map(
                lambda c, n: lax.dynamic_update_slice_in_dim(c, n[None], li + i,
                                                             axis=0),
                scan_cache, nc)
        return (h, scan_cache, li + cfg.pattern_len), ()

    (x, new_scan, _), _ = lax.scan(group_body, (x, cache["scan"], jnp.int32(0)),
                                   params["layers"])
    new_cache = {"scan": new_scan}
    if new_prefix:
        new_cache["prefix"] = jax.tree.map(lambda *xs: jnp.stack(xs), *new_prefix)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    logits = _lm_logits(ctx, params, cfg, x)
    return logits, new_cache


def _lm_logits(ctx, params, cfg, x):
    """Decode-time logits [B, 1, V] vocab-sharded over tp."""
    table = params["embed"]["table"]
    logits = jnp.einsum("bsd,vd->bsv", x.astype(cfg.cdtype),
                        table.astype(cfg.cdtype)).astype(jnp.float32)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


# --- paged serving (continuous batching) ---------------------------------
def init_paged_pool(cfg: TransformerConfig, num_blocks: int, block_size: int):
    """Zeroed paged KV block pools shared by all in-flight requests.

    Layout: {"scan": {"k": [L, NB, block, Hkv * hd], "v": ...}} (+
    "prefix" for dense-prefix layers), heads flattened into the lane
    dimension; blocks are sharded over tp, mapped to
    requests via host-side block tables (repro.serve.kv_cache).  GQA only
    — MLA keeps the dense latent cache for now (registry gates on
    ``supports_paged``)."""
    if cfg.attn_type != "gqa":
        raise NotImplementedError(
            f"paged KV requires attn_type='gqa' ({cfg.name} is {cfg.attn_type})")

    def one(n):
        shape = (n, num_blocks, block_size, cfg.n_kv_heads * cfg.hd)
        return {"k": jnp.zeros(shape, cfg.cdtype),
                "v": jnp.zeros(shape, cfg.cdtype)}

    pool = {"scan": one(cfg.n_layers - cfg.dense_prefix)}
    if cfg.dense_prefix:
        pool["prefix"] = one(cfg.dense_prefix)
    return pool


def pool_logical_specs(cfg: TransformerConfig, pool):
    """Logical sharding specs for a paged pool: [L, NB(blocks/tp), ...]."""
    def spec(x):
        return (None, "seq") + (None,) * (x.ndim - 2)
    return jax.tree.map(spec, pool)


def _attn_serve(ctx, cfg: TransformerConfig, lp, x, pool, layer, tables,
                positions, valid, window):
    """Chunked attention against layer ``layer`` of the stacked paged
    pool.  x: [B, C, D]; positions [B, C] are per-slot global offsets
    (decode: C=1 at pos; prefill: a C-token chunk starting at pos);
    ``valid`` masks padding/idle rows out of the cache write.  The
    chunk's own KV lands in the pool *before* attention, so one causal
    pass covers both the cache and intra-chunk dependencies.  Returns
    the attention output and the updated pool."""
    B, C, D = x.shape
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        qkv = h @ lp["attn"]["w_qkv"]
        q, k, v = jnp.split(qkv, [Hq * hd, (Hq + Hkv) * hd], axis=-1)
        q = q.reshape(B, C, Hq, hd)
        k = k.reshape(B, C, Hkv, hd)
        v = v.reshape(B, C, Hkv, hd)
        rpos = positions
        if cfg.rope_style == "mrope":   # text-phase serving: three equal streams
            rpos = jnp.broadcast_to(positions[None], (3, B, C))
        q = _apply_rope_any(cfg, q, rpos)
        k = _apply_rope_any(cfg, k, rpos)
    with jax.named_scope("attn.kv_write"):
        kc = paged_cache_update(ctx, pool["k"], k.reshape(B, C, Hkv * hd),
                                tables, positions, valid, layer)
        vc = paged_cache_update(ctx, pool["v"], v.reshape(B, C, Hkv * hd),
                                tables, positions, valid, layer)
    with jax.named_scope("attn.paged"):
        o = paged_attention(ctx, q, kc, vc, tables, positions, layer=layer,
                            n_new=valid.sum(axis=1, dtype=jnp.int32),
                            window=window, scale=cfg.query_scale,
                            softcap_val=cfg.attn_softcap)
    with jax.named_scope("attn.out"):
        out = o.reshape(B, C, Hq * hd) @ lp["attn"]["w_o"]
    return out, {"k": kc, "v": vc}


def _layer_serve(ctx, cfg, lp, x, pool, layer, tables, positions, valid,
                 window):
    a, new_pool = _attn_serve(ctx, cfg, lp, x, pool, layer, tables,
                              positions, valid, window)
    if cfg.post_norms:
        a = rms_norm(a, lp["post_ln1"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
    x = x + a
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.norm_plus_one)
        if cfg.moe is not None and "router" in lp["ffn"]:
            f = moe_mod.moe_apply(ctx, lp["ffn"], h, cfg.moe)
        else:
            f = mlp_apply(ctx, lp["ffn"], h, act=cfg.act, seq_sharded=False)
        if cfg.post_norms:
            f = rms_norm(f, lp["post_ln2"], cfg.norm_eps,
                         plus_one=cfg.norm_plus_one)
    return x + f, new_pool


def serve_step(ctx: ParallelContext, params, cfg: TransformerConfig,
               tokens, pool, tables, pos, n_new):
    """One continuous-batching step mixing prefill chunks and decode.

    tokens: [B, C] (slot i's next n_new[i] tokens, zero-padded); tables:
    [B, MB] global block ids; pos: [B] first new position per slot;
    n_new: [B] with 0 = idle slot, 1 = decode step, >1 = prefill chunk.
    C is static, so jit traces exactly two graphs per engine: the
    chunked-prefill graph (C = chunk) and the decode fast path (C = 1).
    Returns (last-valid logits [B, V] f32, updated pool)."""
    B, C = tokens.shape
    pos = broadcast_pos(pos, B)
    n_new = jnp.asarray(n_new, jnp.int32)
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    valid = jnp.arange(C)[None, :] < n_new[:, None]
    scale = cfg.d_model ** 0.5 if cfg.embed_scale else None
    with jax.named_scope("embed"):
        x = embedding_lookup(ctx, params["embed"], tokens, seq_shard=False,
                             scale=scale).astype(cfg.cdtype)

    new_pool = dict(pool)
    for i, lp in enumerate(params.get("prefix", [])):
        x, new_pool["prefix"] = _layer_serve(
            ctx, cfg, lp["l0"], x, new_pool["prefix"], i, tables, positions,
            valid, cfg.layer_window(0))

    def group_body(carry, group_params):
        h, scan_pool, li = carry
        for i in range(cfg.pattern_len):
            h, scan_pool = _layer_serve(ctx, cfg, group_params[f"l{i}"], h,
                                        scan_pool, li + i, tables, positions,
                                        valid, cfg.layer_window(i))
        return (h, scan_pool, li + cfg.pattern_len), ()

    (x, new_pool["scan"], _), _ = lax.scan(
        group_body, (x, pool["scan"], jnp.int32(0)), params["layers"])

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                     plus_one=cfg.norm_plus_one)
        # each slot's logits come from its last *valid* token (prefill
        # chunks only need the final position; idle slots produce garbage,
        # discarded)
        idx = jnp.clip(n_new - 1, 0, C - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # [B,1,D]
        logits = _lm_logits(ctx, params, cfg, x_last)
    return logits[:, 0], new_pool
