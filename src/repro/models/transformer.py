"""Decoder-only transformer assembly: scanned block groups + cache trees.

An architecture is a repeated ``block_pattern`` group (scanned ``n_groups``
times with stacked params — one traced group regardless of depth, keeping
HLO size and compile time flat) plus optional unscanned ``tail_pattern``
blocks.  Heterogeneous patterns (gemma2 local/global pairs, recurrentgemma
rglru/rglru/local triples) scan cleanly because each *position* in the group
has homogeneous params/caches across groups.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamSpec, dense, dense_spec, mlp, mlp_spec,
                                 norm_spec, padded_vocab, rmsnorm, softcap,
                                 stack_specs)
from repro.models.moe import moe_block, moe_spec
from repro.sharding import shard

ATTN_KINDS = ("attn", "local", "global")


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def block_spec(cfg, kind: str):
    d = cfg.d_model
    if kind == "ssm":
        return {"ln": norm_spec(d), "mixer": ssm_mod.ssm_spec(cfg)}
    if kind == "rglru":
        s = {"ln1": norm_spec(d), "rec": rglru_mod.rglru_spec(cfg),
             "ln2": norm_spec(d), "ffn": mlp_spec(cfg)}
        return s
    assert kind in ATTN_KINDS, kind
    ffn = moe_spec(cfg) if cfg.num_experts else mlp_spec(cfg)
    s = {"ln1": norm_spec(d), "attn": attn_mod.attn_spec(cfg),
         "ln2": norm_spec(d), "ffn": ffn}
    if cfg.post_norms:
        s["pn1"] = norm_spec(d)
        s["pn2"] = norm_spec(d)
    return s


def _window_for(cfg, kind: str) -> int:
    if kind == "local":
        return cfg.local_window
    if kind == "global":
        return 0
    return cfg.sliding_window


def block_cache_spec(cfg, kind: str, batch: int, cache_len: int):
    if kind == "ssm":
        return ssm_mod.make_ssm_cache_spec(cfg, batch)
    if kind == "rglru":
        return rglru_mod.make_rglru_cache_spec(cfg, batch)
    return attn_mod.make_attn_cache_spec(cfg, batch, cache_len,
                                         _window_for(cfg, kind))


def decoder_specs(cfg):
    """Param specs for the block stack (no embeddings)."""
    groups = tuple(stack_specs(block_spec(cfg, k), cfg.n_groups)
                   for k in cfg.block_pattern)
    tail = tuple(block_spec(cfg, k) for k in cfg.tail_pattern)
    return {"groups": groups, "tail": tail,
            "final_norm": norm_spec(cfg.d_model)}


def decoder_cache_specs(cfg, batch: int, cache_len: int):
    groups = tuple(
        stack_specs(block_cache_spec(cfg, k, batch, cache_len), cfg.n_groups)
        for k in cfg.block_pattern)
    tail = tuple(block_cache_spec(cfg, k, batch, cache_len)
                 for k in cfg.tail_pattern)
    return {"groups": groups, "tail": tail}


def embed_specs(cfg):
    vp = padded_vocab(cfg)
    out = {"tok": ParamSpec((vp, cfg.d_model), axes=("vocab", "w_embed"),
                            scale=24.0)}
    if not cfg.tie_embeddings:
        out["lm_head"] = dense_spec(cfg.d_model, vp, ("w_embed", "vocab"))
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def block_forward(cfg, kind: str, p, x, *, mode: str, cache, positions,
                  layer=0):
    """One block.  An attention block in decode mode takes and returns the
    stack of its position's layer caches, ``layer`` indexing its own
    (``attention``); every other block takes and returns its own cache."""
    if kind == "ssm":
        h, nc = ssm_mod.ssm_block(cfg, p["mixer"],
                                  rmsnorm(p["ln"], x, cfg.norm_eps),
                                  mode=mode, cache=cache)
        return x + h, nc
    if kind == "rglru":
        h, nc = rglru_mod.rglru_block(cfg, p["rec"],
                                      rmsnorm(p["ln1"], x, cfg.norm_eps),
                                      mode=mode, cache=cache)
        x = x + h
        x = x + mlp(cfg, p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps))
        return x, nc
    # attention blocks
    h, nc = attn_mod.attention(cfg, p["attn"],
                               rmsnorm(p["ln1"], x, cfg.norm_eps),
                               positions=positions, mode=mode, cache=cache,
                               layer=layer, window=_window_for(cfg, kind))
    if cfg.post_norms:
        h = rmsnorm(p["pn1"], h, cfg.norm_eps)
    x = x + h
    h2 = rmsnorm(p["ln2"], x, cfg.norm_eps)
    h2 = moe_block(cfg, p["ffn"], h2) if cfg.num_experts \
        else mlp(cfg, p["ffn"], h2)
    if cfg.post_norms:
        h2 = rmsnorm(p["pn2"], h2, cfg.norm_eps)
    return x + h2, nc


def _decodes_into_stack(mode: str, kind: str) -> bool:
    return mode == "decode" and kind in ATTN_KINDS


def run_decoder(cfg, params, x, *, mode: str, caches=None, positions=None,
                remat: bool = False):
    """x (B,S,D) -> (y (B,S,D), new_caches).

    With caches, the stacked cache tree rides in the scan CARRY, so XLA
    updates it in place across the layer loop.  (Passing caches as scan
    xs/ys materializes full stacked input AND output buffers as temps:
    several extra cache-sized copies per step, blowing the 16 GiB budget
    for 70B-class decode.)

    In decode, an attention block gets its position's whole KV stack and
    the layer index: it scatters the new token into the stack at
    ``[layer, row, pos % T]`` and reads its layer from the stack read-only
    for the attention math.  Slicing the layer's (B,T,K,D) slab out and
    writing it back instead copies the slab out of the carry and in again,
    K and V, every layer and step, to store B tokens.  SSM and RG-LRU
    state is rewritten whole every step, and prefill writes whole slabs,
    so those keep the slice / write-back."""
    pattern = cfg.block_pattern
    has_cache = caches is not None
    from repro.tracemode import scan_unroll

    if not has_cache:
        def group_fn(carry, gp):
            for i, kind in enumerate(pattern):
                carry, _ = block_forward(cfg, kind, gp[i], carry, mode=mode,
                                         cache=None, positions=positions)
            # the scan carry is what remat saves per group; "seq_remat"
            # (None by default) lets wide models store it seq-sharded
            carry = shard(carry, "batch", "seq_remat", "embed")
            return carry, None

        if remat:
            group_fn = jax.checkpoint(
                group_fn, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(group_fn, x, params["groups"],
                            unroll=scan_unroll())
        group_caches = None
    else:
        def group_fn(carry, xs):
            h, gcaches = carry
            gp, gi = xs
            new_gc = []
            for i, kind in enumerate(pattern):
                if _decodes_into_stack(mode, kind):
                    h, gc = block_forward(cfg, kind, gp[i], h, mode=mode,
                                          cache=gcaches[i],
                                          positions=positions, layer=gi)
                else:
                    c = jax.tree.map(
                        lambda l: jax.lax.dynamic_index_in_dim(
                            l, gi, 0, keepdims=False), gcaches[i])
                    h, nc = block_forward(cfg, kind, gp[i], h, mode=mode,
                                          cache=c, positions=positions)
                    gc = jax.tree.map(
                        lambda l, n: jax.lax.dynamic_update_index_in_dim(
                            l, n, gi, 0), gcaches[i], nc)
                new_gc.append(gc)
            h = shard(h, "batch", "seq", "embed")
            return (h, tuple(new_gc)), None

        gi = jnp.arange(cfg.n_groups, dtype=jnp.int32)
        (x, group_caches), _ = jax.lax.scan(
            group_fn, (x, caches["groups"]), (params["groups"], gi),
            unroll=scan_unroll())

    tail_caches = []
    for i, kind in enumerate(cfg.tail_pattern):
        c = caches["tail"][i] if has_cache else None
        stacked = _decodes_into_stack(mode, kind)
        x, nc = block_forward(cfg, kind, params["tail"][i], x, mode=mode,
                              cache=attn_mod.stack_of_one(c) if stacked
                              else c, positions=positions)
        tail_caches.append(jax.tree.map(lambda l: l[0], nc) if stacked
                           else nc)

    new_caches = ({"groups": group_caches, "tail": tuple(tail_caches)}
                  if has_cache else None)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, new_caches


def embed_tokens(cfg, embed_params, tokens, vision_embeds=None):
    x = jnp.take(embed_params["tok"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
    if vision_embeds is not None:
        n = vision_embeds.shape[1]
        x = jax.lax.dynamic_update_slice_in_dim(
            x, vision_embeds.astype(x.dtype), 0, 1)  # stub patches at front
        del n
    return shard(x, "batch", "seq", "embed")


def lm_logits(cfg, embed_params, x):
    if cfg.tie_embeddings:
        logits = x @ embed_params["tok"].T
    else:
        logits = dense(embed_params["lm_head"], x)
    logits = softcap(logits, cfg.final_logit_softcap)
    return shard(logits, "batch", "seq", "vocab")
