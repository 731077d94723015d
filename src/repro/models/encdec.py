"""Encoder-decoder backbone (SeamlessM4T-medium).  [arXiv:2308.11596]

The modality frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, src_len, d_model) from ``input_specs()``.
Backbone approximations vs the HF checkpoint: RoPE in place of learned
positions (noted in DESIGN.md).  Decoder = causal self-attention (cached) +
cross-attention (cross-KV cached at prefill) + FFN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.attention import (_scores, _weighted, _split_heads,
                                    make_attn_cache_spec)
from repro.models.layers import (apply_rope, dense, dense_spec, mlp,
                                 mlp_spec, norm_spec, rmsnorm, stack_specs)
from repro.sharding import shard


def _cross_attn_spec(cfg):
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "q": dense_spec(cfg.d_model, hq * dh, ("w_embed", "heads")),
        "k": dense_spec(cfg.d_model, hkv * dh, ("w_embed", "kv_heads")),
        "v": dense_spec(cfg.d_model, hkv * dh, ("w_embed", "kv_heads")),
        "o": dense_spec(hq * dh, cfg.d_model, ("heads", "w_embed")),
    }


def enc_block_spec(cfg):
    return {"ln1": norm_spec(cfg.d_model), "attn": attn_mod.attn_spec(cfg),
            "ln2": norm_spec(cfg.d_model), "ffn": mlp_spec(cfg)}


def dec_block_spec(cfg):
    return {"ln1": norm_spec(cfg.d_model), "self": attn_mod.attn_spec(cfg),
            "ln2": norm_spec(cfg.d_model), "cross": _cross_attn_spec(cfg),
            "ln3": norm_spec(cfg.d_model), "ffn": mlp_spec(cfg)}


def encoder_specs(cfg):
    return {"src_proj": dense_spec(cfg.d_model, cfg.d_model,
                                   ("w_embed", None)),
            "blocks": stack_specs(enc_block_spec(cfg), cfg.encoder_layers),
            "final_norm": norm_spec(cfg.d_model)}


def decoder_specs(cfg):
    return {"blocks": stack_specs(dec_block_spec(cfg), cfg.num_layers),
            "final_norm": norm_spec(cfg.d_model)}


def dec_cache_specs(cfg, batch: int, cache_len: int):
    self_spec = make_attn_cache_spec(cfg, batch, cache_len)
    cross = make_attn_cache_spec(cfg, batch, cfg.encoder_src_len)
    block = {"self": self_spec, "cross": cross}
    return {"blocks": stack_specs(block, cfg.num_layers)}


# ---------------------------------------------------------------------------


def _bidir_attention(cfg, p, x, positions):
    """Full bidirectional self-attention (encoder)."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b, s = x.shape[:2]
    q = apply_rope(_split_heads(dense(p["q"], x), hq, dh), positions,
                   cfg.rope_theta)
    k = apply_rope(_split_heads(dense(p["k"], x), hkv, dh), positions,
                   cfg.rope_theta)
    v = _split_heads(dense(p["v"], x), hkv, dh)
    qg = q.reshape(b, s, hkv, hq // hkv, dh)
    w = jax.nn.softmax(_scores(qg, k, dh ** -0.5, 0.0), axis=-1)
    out = _weighted(v, w).reshape(b, s, hq * dh)
    return dense(p["o"], out)


def run_encoder(cfg, params, frames):
    """frames (B, src, D) stub embeddings -> encoder output (B, src, D)."""
    x = dense(params["src_proj"], frames.astype(jnp.bfloat16))
    x = shard(x, "batch", "src", "embed")
    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    def block(carry, p):
        h = _bidir_attention(cfg, p["attn"],
                             rmsnorm(p["ln1"], carry, cfg.norm_eps), pos)
        carry = carry + h
        carry = carry + mlp(cfg, p["ffn"],
                            rmsnorm(p["ln2"], carry, cfg.norm_eps))
        return carry, None

    from repro.tracemode import scan_unroll
    x, _ = jax.lax.scan(block, x, params["blocks"], unroll=scan_unroll())
    return rmsnorm(params["final_norm"], x, cfg.norm_eps)


def _cross_attention(cfg, p, x, enc_out=None, cache=None):
    """Decoder cross-attention.  At prefill/train ``enc_out`` is given and
    cross-KV is computed (and cached); at decode the cache is used."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b, s = x.shape[:2]
    q = _split_heads(dense(p["q"], x), hq, dh)
    if enc_out is not None:
        k = _split_heads(dense(p["k"], enc_out), hkv, dh)
        v = _split_heads(dense(p["v"], enc_out), hkv, dh)
    else:
        k, v = cache["k"], cache["v"]
    qg = q.reshape(b, s, hkv, hq // hkv, dh)
    w = jax.nn.softmax(_scores(qg, k, dh ** -0.5, 0.0), axis=-1)
    out = _weighted(v, w).reshape(b, s, hq * dh)
    new_cache = {"k": k, "v": v} if cache is not None else None
    return dense(p["o"], out), new_cache


def run_decoder(cfg, params, x, *, mode: str, caches=None, positions=None,
                enc_out=None, remat: bool = False):
    """Decoder over token embeddings x (B,S,D)."""
    has_cache = caches is not None

    from repro.tracemode import scan_unroll

    def body(h, p, c, layer=0):
        hh, self_c = attn_mod.attention(
            cfg, p["self"], rmsnorm(p["ln1"], h, cfg.norm_eps),
            positions=positions, mode=mode,
            cache=c["self"] if has_cache else None, layer=layer)
        h = h + hh
        hh, cross_c = _cross_attention(
            cfg, p["cross"], rmsnorm(p["ln2"], h, cfg.norm_eps),
            enc_out=enc_out, cache=c["cross"] if has_cache else None)
        h = h + hh
        h = h + mlp(cfg, p["ffn"], rmsnorm(p["ln3"], h, cfg.norm_eps))
        nc = {"self": self_c, "cross": cross_c} if has_cache else None
        return h, nc

    if not has_cache:
        def block(carry, p):
            h, _ = body(carry, p, None)
            return h, None

        if remat:
            block = jax.checkpoint(
                block, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(block, x, params["blocks"],
                            unroll=scan_unroll())
        new_caches = None
    else:
        # caches ride in the carry (see transformer.run_decoder).  In
        # decode, self-attention writes its token into the whole stack in
        # place and the cross-attention cache is only read, so no layer's
        # slab is written back; prefill writes each layer's whole.
        def layer_of(tree, bi):
            return jax.tree.map(lambda l: jax.lax.dynamic_index_in_dim(
                l, bi, 0, keepdims=False), tree)

        def block(carry, xs):
            h, bcaches = carry
            p, bi = xs
            if mode == "decode":
                h, nc = body(h, p, {"self": bcaches["self"],
                                    "cross": layer_of(bcaches["cross"], bi)},
                             layer=bi)
                return (h, {"self": nc["self"],
                            "cross": bcaches["cross"]}), None
            h, nc = body(h, p, layer_of(bcaches, bi))
            bcaches = jax.tree.map(
                lambda l, n: jax.lax.dynamic_update_index_in_dim(
                    l, n, bi, 0), bcaches, nc)
            return (h, bcaches), None

        bi = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, blocks), _ = jax.lax.scan(
            block, (x, caches["blocks"]), (params["blocks"], bi),
            unroll=scan_unroll())
        new_caches = {"blocks": blocks}
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), new_caches
