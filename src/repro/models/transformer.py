"""Dense decoder-only transformer family.

Covers gemma-7b (GeGLU, head_dim 256, embed scaling), h2o-danube (SWA),
qwen2 (QKV bias), internvl2 (vision-prefix overlay), minicpm3 (MLA via
``models/mla.py``) and the MoE archs (FFN via ``models/moe.py``).

Layers are stacked on a leading "layers" axis and executed with
``lax.scan`` (+ per-layer ``jax.checkpoint``); the residual stream is
sequence-sharded between layers (constrain "seq" -> "model") and gathered
inside blocks — Megatron-style sequence parallelism, which keeps saved
activations 1/TP-degree sized.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.nn.param import ParamSpec, is_spec
from repro.nn import layers as L
from repro.nn.rope import apply_rope
from repro.nn.attention import chunked_attention, decode_attention
from repro.dist.sharding import constrain
from repro.models import moe as moe_lib
from repro.models import mla as mla_lib


# -- specs -------------------------------------------------------------------

def norm_spec(cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    return (L.rmsnorm_spec if cfg.norm == "rmsnorm" else L.layernorm_spec)(
        dim, cfg.param_dtype)


def apply_norm(cfg: ModelConfig, p, x):
    return (L.rmsnorm if cfg.norm == "rmsnorm" else L.layernorm)(p, x)


def attn_spec(cfg: ModelConfig):
    if cfg.mla is not None:
        return mla_lib.mla_spec(cfg)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    p = {
        "wq": ParamSpec((d, hq * hd), dt, "scaled", ("embed", "heads")),
        "wk": ParamSpec((d, hkv * hd), dt, "scaled", ("embed", "kv_heads")),
        "wv": ParamSpec((d, hkv * hd), dt, "scaled", ("embed", "kv_heads")),
        "wo": ParamSpec((hq * hd, d), dt, "scaled", ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((hq * hd,), dt, "zeros", ("heads",))
        p["bk"] = ParamSpec((hkv * hd,), dt, "zeros", ("kv_heads",))
        p["bv"] = ParamSpec((hkv * hd,), dt, "zeros", ("kv_heads",))
    return p


def mlp_spec(cfg: ModelConfig):
    if cfg.moe is not None:
        return moe_lib.moe_spec(cfg)
    return L.mlp_spec(cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                      dtype=cfg.param_dtype)


def layer_spec(cfg: ModelConfig):
    return {
        "attn_norm": norm_spec(cfg),
        "attn": attn_spec(cfg),
        "mlp_norm": norm_spec(cfg),
        "mlp": mlp_spec(cfg),
    }


def stack_specs(tree, n: int):
    """Add a leading 'layers' axis to every ParamSpec leaf (scan storage)."""
    def one(s: ParamSpec):
        return ParamSpec((n,) + s.shape, s.dtype, s.init,
                         ("layers",) + tuple(s.axes), s.scale)
    return jax.tree.map(one, tree, is_leaf=is_spec)


def params_spec(cfg: ModelConfig):
    return {
        "embed": L.embedding_spec(cfg.vocab, cfg.d_model, cfg.param_dtype),
        "layers": stack_specs(layer_spec(cfg), cfg.n_layers),
        "final_norm": norm_spec(cfg),
    }


# -- forward -------------------------------------------------------------------

def _qkv(p, cfg: ModelConfig, x):
    cd = cfg.compute_dtype
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xc = x.astype(cd)
    q = jnp.einsum("bsd,de->bse", xc, p["wq"].astype(cd))
    k = jnp.einsum("bsd,de->bse", xc, p["wk"].astype(cd))
    v = jnp.einsum("bsd,de->bse", xc, p["wv"].astype(cd))
    if "bq" in p:
        q, k, v = (q + p["bq"].astype(cd), k + p["bk"].astype(cd),
                   v + p["bv"].astype(cd))
    return (q.reshape(b, s, hq, hd), k.reshape(b, s, hkv, hd),
            v.reshape(b, s, hkv, hd))


def self_attention(p, cfg: ModelConfig, x, positions, *, collect_kv=False):
    if cfg.mla is not None:
        return mla_lib.mla_attention(p, cfg, x, positions,
                                     return_cache=collect_kv)
    cd = cfg.compute_dtype
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    out = chunked_attention(q, k, v, causal=True, window=cfg.window,
                            q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    out = constrain(out, ("batch", None, "heads", None))
    o = jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1),
                   p["wo"].astype(cd))
    return (o, (k, v)) if collect_kv else o


def block(p, cfg: ModelConfig, x, positions):
    """Pre-norm residual block. Returns (x, aux)."""
    h = self_attention(p["attn"], cfg, apply_norm(cfg, p["attn_norm"], x),
                       positions)
    x = constrain(x + h.astype(x.dtype), ("batch", "seq", None))
    xm = apply_norm(cfg, p["mlp_norm"], x)
    if cfg.moe is not None:
        m, aux = moe_lib.moe_apply(p["mlp"], cfg, xm)
    else:
        m = L.mlp(p["mlp"], xm, act=cfg.act, compute_dtype=cfg.compute_dtype)
        aux = jnp.float32(0.0)
    x = constrain(x + m.astype(x.dtype), ("batch", "seq", None))
    return x, aux


def embed_tokens(params, cfg: ModelConfig, tokens, vision_embeds=None):
    x = L.embed(params["embed"], tokens, cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.vision_prefix and vision_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, vision_embeds.astype(x.dtype), (0, 0, 0))
    return x


def forward_hidden(params, cfg: ModelConfig, tokens, vision_embeds=None):
    """tokens (B, S) -> (hidden (B, S, d), aux scalar)."""
    b, s = tokens.shape
    x = embed_tokens(params, cfg, tokens, vision_embeds)
    x = constrain(x, ("batch", "seq", None))
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(carry, lp):
        x, aux = carry
        x, a = block(lp, cfg, x, positions)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    return x, aux


# -- prefill -------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens, vision_embeds=None,
            cache_seq: Optional[int] = None):
    """Forward over the prompt, collecting the decode cache.

    Returns (last-token logits (B, V), cache positioned at pos = S).
    ``cache_seq`` sizes the cache for subsequent decoding (>= S; defaults
    to S — the dry-run's prefill cell).
    """
    b, s = tokens.shape
    total = cache_seq or s
    c = cache_len(cfg, total)
    keep = min(c, s)                 # last `keep` prompt entries are cached
    x = embed_tokens(params, cfg, tokens, vision_embeds)
    x = constrain(x, ("batch", "seq", None))
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def body(carry, lp):
        x, aux = carry
        h, kv = self_attention(lp["attn"], cfg,
                               apply_norm(cfg, lp["attn_norm"], x),
                               positions, collect_kv=True)
        x = constrain(x + h.astype(x.dtype), ("batch", "seq", None))
        xm = apply_norm(cfg, lp["mlp_norm"], x)
        if cfg.moe is not None:
            m, a = moe_lib.moe_apply(lp["mlp"], cfg, xm)
        else:
            m = L.mlp(lp["mlp"], xm, act=cfg.act,
                      compute_dtype=cfg.compute_dtype)
            a = jnp.float32(0.0)
        x = constrain(x + m.astype(x.dtype), ("batch", "seq", None))
        if cfg.mla is not None:
            entry = kv[:, s - keep:]
        else:
            entry = tuple(t[:, s - keep:] for t in kv)
        return (x, aux + a), entry

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, _), entries = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(params["embed"], x[:, -1], cfg.compute_dtype)

    def place(entry, width_shape):
        buf = jnp.zeros(width_shape, entry.dtype)
        # ring-consistent placement: prompt entry i lands in slot (s-keep+i)%c
        start = (s - keep) % c
        return jax.lax.dynamic_update_slice_in_dim(buf, entry, start, axis=2)

    pos = jnp.int32(s)
    if cfg.mla is not None:
        w = mla_lib.mla_cache_width(cfg)
        ckv = place(entries, (cfg.n_layers, b, c, w))
        return logits, {"ckv": ckv, "pos": pos}
    ks = place(entries[0],
               (cfg.n_layers, b, c, cfg.n_kv_heads, cfg.head_dim))
    vs = place(entries[1],
               (cfg.n_layers, b, c, cfg.n_kv_heads, cfg.head_dim))
    return logits, {"k": ks, "v": vs, "pos": pos}


# -- decode --------------------------------------------------------------------

def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    return min(seq_len, cfg.window) if cfg.window else seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    """Zeroed decode state; see ``cache_spec`` for the dry-run structs."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, seq_len))


def cache_spec(cfg: ModelConfig, batch: int, seq_len: int):
    c = cache_len(cfg, seq_len)
    lcount = cfg.n_layers
    cd = cfg.compute_dtype
    if cfg.mla is not None:
        w = mla_lib.mla_cache_width(cfg)
        return {
            "ckv": jax.ShapeDtypeStruct((lcount, batch, c, w), cd),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
    return {
        "k": jax.ShapeDtypeStruct(
            (lcount, batch, c, cfg.n_kv_heads, cfg.head_dim), cd),
        "v": jax.ShapeDtypeStruct(
            (lcount, batch, c, cfg.n_kv_heads, cfg.head_dim), cd),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cache_logical_axes(cfg: ModelConfig):
    """Logical sharding axes for the cache pytree (pos replicated)."""
    if cfg.mla is not None:
        return {"ckv": (None, "batch", "seq", None), "pos": ()}
    kv = (None, "batch", "seq", "kv_heads", None)
    return {"k": kv, "v": kv, "pos": ()}


def _ring_slot(pos, c):
    return pos % c


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """One-token decode. tokens (B,) -> (logits (B, V), new cache).

    The cache is written at ``pos % C`` (ring semantics; for SWA the ring
    IS the window, for full attention C == seq_len and the dry-run drives
    pos < C). Attention masks slots beyond min(pos+1, C).
    """
    b = tokens.shape[0]
    cd = cfg.compute_dtype
    pos = cache["pos"]
    x = embed_tokens(params, cfg, tokens[:, None])          # (B, 1, d)
    x = x[:, 0]                                             # (B, d)

    # The cache is a scan CARRY (not xs/ys): while-loop carries alias
    # in-place under donation, so the decode step's live memory is ONE cache
    # buffer — scan xs->ys stacking would triple it (measured 27 GB vs 9 GB
    # on gemma decode_32k).
    if cfg.mla is not None:
        def body(carry, args):
            x, ckv = carry
            i, lp = args
            ckv_l = jax.lax.dynamic_index_in_dim(ckv, i, 0, keepdims=False)
            h, ckv_l = mla_lib.mla_decode_step(
                lp["attn"], cfg, apply_norm(cfg, lp["attn_norm"], x),
                ckv_l, pos)
            ckv = jax.lax.dynamic_update_index_in_dim(ckv, ckv_l, i, 0)
            x = x + h.astype(x.dtype)
            x = x + _mlp_1tok(lp, cfg, x)
            return (x, ckv), None

        (x, ckv), _ = jax.lax.scan(
            body, (x, cache["ckv"]),
            (jnp.arange(cfg.n_layers), params["layers"]))
        new_cache = {"ckv": ckv, "pos": pos + 1}
    else:
        c = cache["k"].shape[2]
        slot = _ring_slot(pos, c)
        length = jnp.broadcast_to(jnp.minimum(pos + 1, c), (b,))

        def body(carry, args):
            x, ks, vs = carry
            i, lp = args
            kc = jax.lax.dynamic_index_in_dim(ks, i, 0, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(vs, i, 0, keepdims=False)
            xa = apply_norm(cfg, lp["attn_norm"], x)[:, None, :]
            q, k1, v1 = _qkv(lp["attn"], cfg, xa)
            posb = jnp.full((b, 1), pos)
            q = apply_rope(q, posb, theta=cfg.rope_theta)[:, 0]
            k1 = apply_rope(k1, posb, theta=cfg.rope_theta)
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k1, slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v1, slot, axis=1)
            att = decode_attention(q, kc, vc, length=length)
            h = jnp.einsum("be,ed->bd", att.reshape(b, -1),
                           lp["attn"]["wo"].astype(cd))
            x = x + h.astype(x.dtype)
            x = x + _mlp_1tok(lp, cfg, x)
            ks = jax.lax.dynamic_update_index_in_dim(ks, kc, i, 0)
            vs = jax.lax.dynamic_update_index_in_dim(vs, vc, i, 0)
            return (x, ks, vs), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(cfg.n_layers), params["layers"]))
        new_cache = {"k": ks, "v": vs, "pos": pos + 1}

    x = apply_norm(cfg, params["final_norm"], x)
    logits = L.unembed(params["embed"], x, cd)              # (B, V)
    return logits, new_cache


def _mlp_1tok(lp, cfg: ModelConfig, x):
    xm = apply_norm(cfg, lp["mlp_norm"], x)
    if cfg.moe is not None:
        m, _ = moe_lib.moe_apply(lp["mlp"], cfg, xm[:, None, :])
        return m[:, 0].astype(x.dtype)
    return L.mlp(lp["mlp"], xm, act=cfg.act,
                 compute_dtype=cfg.compute_dtype).astype(x.dtype)
