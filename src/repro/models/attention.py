"""Attention: GQA/MQA, RoPE/M-RoPE, full/sliding-window, softcap, KV caches.

Three modes share one entry point:
  * ``train``   — no cache, causal (optionally windowed) mask.
  * ``prefill`` — as train, but also writes the partition KV cache.
  * ``decode``  — one query token per row against the cache; per-row
                  positions support continuous batching (rows advance
                  independently).  Ring caches (T == window) support
                  unbounded contexts for SWA/local layers.

Long sequences (>= FLASH_SEQ) use a chunked online-softmax path so prefill
at 32k never materializes an S x S score matrix.  The Pallas decode kernels
in ``repro.kernels`` implement the same math for the TPU hot path; this XLA
formulation is what the dry-run lowers (identical FLOPs).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (ParamSpec, apply_rope, dense, dense_spec,
                                 f32, softcap)
from repro.sharding import shard

# chunked online-softmax attention at/above this length.  NOTE (hillclimb
# A3, refuted): lowering this to 4096 for train does NOT bound backward
# memory — under jax.checkpoint the scan backward still saves per-chunk
# probabilities (O(S^2) f32).  A custom-VJP flash kernel is the real lever.
FLASH_SEQ = 8192
Q_CHUNK = 512
KV_CHUNK = 1024
NEG_INF = -2.0 ** 30


def attn_spec(cfg):
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "q": dense_spec(cfg.d_model, hq * dh, ("w_embed", "heads"),
                        bias=cfg.qkv_bias),
        "k": dense_spec(cfg.d_model, hkv * dh, ("w_embed", "kv_heads"),
                        bias=cfg.qkv_bias),
        "v": dense_spec(cfg.d_model, hkv * dh, ("w_embed", "kv_heads"),
                        bias=cfg.qkv_bias),
        "o": dense_spec(hq * dh, cfg.d_model, ("heads", "w_embed")),
    }


def _split_heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


def _scores(q, k, scale, cap):
    """q (B,S,K,G,D) x k (B,T,K,D) -> (B,K,G,S,T) fp32, softcapped."""
    s = jnp.einsum("bskgd,btkd->bkgst", q, k,
                   preferred_element_type=f32) * scale
    return softcap(s, cap)


def _weighted(v, w):
    """w (B,K,G,S,T) x v (B,T,K,D) -> (B,S,K,G,D)."""
    return jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)


def _plain_attention(q, k, v, qpos, kpos, window, scale, cap):
    s = _scores(q, k, scale, cap)
    mask = kpos[:, None, :] <= qpos[:, :, None]            # causal
    if window:
        mask &= kpos[:, None, :] > qpos[:, :, None] - window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return _weighted(v, w)


def _chunked_attention(q, k, v, qpos, kpos, window, scale, cap):
    """Online-softmax flash attention in pure jnp (scan over chunks)."""
    b, sq, hk, g, d = q.shape
    t = k.shape[1]
    nq, nk = sq // Q_CHUNK, t // KV_CHUNK
    assert sq % Q_CHUNK == 0 and t % KV_CHUNK == 0, (sq, t)
    qc = jnp.moveaxis(q.reshape(b, nq, Q_CHUNK, hk, g, d), 1, 0)
    qpc = jnp.moveaxis(qpos.reshape(b, nq, Q_CHUNK), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, nk, KV_CHUNK, hk, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nk, KV_CHUNK, hk, d), 1, 0)
    kpc = jnp.moveaxis(kpos.reshape(b, nk, KV_CHUNK), 1, 0)

    def q_step(_, qx):
        qi, qp = qx

        def kv_step(carry, kx):
            m, l, acc = carry
            ki, vi, kp = kx
            s = _scores(qi, ki, scale, cap)                 # (B,K,G,Cq,Ck)
            mask = kp[:, None, :] <= qp[:, :, None]
            if window:
                mask &= kp[:, None, :] > qp[:, :, None] - window
            s = jnp.where(mask[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p, vi.astype(f32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hk, g, Q_CHUNK), NEG_INF, f32)
        l0 = jnp.zeros((b, hk, g, Q_CHUNK), f32)
        a0 = jnp.zeros((b, hk, g, Q_CHUNK, d), f32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kpc))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, jnp.moveaxis(out, 3, 1)                # (B,Cq,K,G,D)

    _, out = jax.lax.scan(q_step, None, (qc, qpc))          # (nq,B,Cq,...)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, hk, g, d)
    return out.astype(q.dtype)


def _decode_attention(q, cache_k, cache_v, pos, window, scale, cap):
    """q (B,1,K,G,D) vs ring/linear cache (B,T,K,D); pos (B,) is the global
    position of the *current* token (already written into the cache)."""
    b, t = cache_k.shape[:2]
    slots = jnp.arange(t, dtype=jnp.int32)[None, :]          # (B,T)
    # global index held by each slot (writes go to pos % T)
    gidx = pos[:, None] - ((pos[:, None] - slots) % t)
    valid = gidx >= 0
    if window:
        valid &= gidx > pos[:, None] - window
    s = _scores(q, cache_k, scale, cap)                      # (B,K,G,1,T)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return _weighted(cache_v, w)                             # (B,1,K,G,D)


def attention(cfg, p, x, *, positions, mode: str, cache=None, layer=0,
              window: int = 0):
    """Returns (y, new_cache).  ``positions``: (B,S) [or (3,B,S) M-RoPE] for
    train/prefill; (B,) [or (3,B)] global positions for decode.

    Prefill takes and returns one layer's cache (B,T,K,D).  Decode takes
    the stack of every layer's cache at this block position (L,B,T,K,D)
    and ``layer``, the index of this one; it writes the new token into the
    stack in place and returns the stack (see ``stack_of_one`` for a block
    outside a stack)."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = hq // hkv
    scale = cfg.query_scale or dh ** -0.5
    cap = cfg.attn_logit_softcap
    b, s = x.shape[:2]

    rope_pos = positions if mode != "decode" else (
        positions[..., None])  # (B,1) / (3,B,1)
    q = _split_heads(dense(p["q"], x), hq, dh)
    k = _split_heads(dense(p["k"], x), hkv, dh)
    v = _split_heads(dense(p["v"], x), hkv, dh)
    q = apply_rope(q, rope_pos, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, rope_pos, cfg.rope_theta, cfg.mrope_sections)
    tok_pos = positions[0] if (cfg.mrope_sections and positions.ndim == 3
                               ) else positions
    if cfg.mrope_sections and mode == "decode" and positions.ndim == 2:
        tok_pos = positions[0]

    if mode in ("train", "prefill"):
        q = shard(q, "batch", "seq", "heads", None)
        k = shard(k, "batch", "seq", "kv_heads", None)
        v = shard(v, "batch", "seq", "kv_heads", None)
        qg = q.reshape(b, s, hkv, g, dh)
        from repro.tracemode import is_analysis
        use_flash = s >= FLASH_SEQ and not is_analysis()
        fn = _chunked_attention if use_flash else _plain_attention
        out = fn(qg, k, v, tok_pos, tok_pos, window, scale, cap)
        new_cache = None
        if mode == "prefill":
            t = cache["k"].shape[1]
            if t >= s:                                   # linear fill
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)
            else:                # ring: keep tail; global pos p -> slot p % t
                roll = s % t
                ck = jnp.roll(k[:, -t:], roll, axis=1)
                cv = jnp.roll(v[:, -t:], roll, axis=1)
            new_cache = {
                "k": shard(ck, "batch", "kv_seq", "kv_heads", None),
                "v": shard(cv, "batch", "kv_seq", "kv_heads", None),
            }
    else:
        # Decode: the cache is sequence-sharded over "model" (kv head
        # counts rarely divide 16; at 32k+, T always does).  Heads must be
        # REPLICATED through the attention math — constraining them onto
        # "model" here would conflict with the T-sharding and force the
        # SPMD partitioner into involuntary full rematerialization of the
        # multi-GiB cache.  The o-projection (row-sharded) restores TP via
        # its contraction psum.
        q = shard(q, "batch", None, None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        # One scatter of B tokens into the stack, then a read-only view of
        # this layer for the attention math: the stack stays in place, and
        # no layer-sized slab is copied out of it or back.
        t = cache["k"].shape[2]
        idx = tok_pos % t                                # (B,)
        rows = jnp.arange(b)
        ck = cache["k"].at[layer, rows, idx].set(k[:, 0])
        cv = cache["v"].at[layer, rows, idx].set(v[:, 0])
        ck = shard(ck, None, "batch", "kv_seq", "kv_heads", None)
        cv = shard(cv, None, "batch", "kv_seq", "kv_heads", None)
        qg = q.reshape(b, s, hkv, g, dh)
        out = _decode_attention(
            qg, jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False),
            tok_pos, window, scale, cap)
        out = shard(out.reshape(b, s, hq * dh), "batch", "seq", None)
        return dense(p["o"], out), {"k": ck, "v": cv}

    out = out.reshape(b, s, hq * dh)
    out = shard(out, "batch", "seq", "heads")
    return dense(p["o"], out), new_cache


def stack_of_one(cache):
    """One layer's cache (B,T,K,D) as a layer stack of one, the form
    ``attention`` decodes into (index 0; ``l[0]`` takes it back out)."""
    return jax.tree.map(lambda l: l[None], cache)


def make_attn_cache_spec(cfg, batch: int, cache_len: int, window: int = 0):
    """ParamSpec tree for one attention block's KV cache."""
    t = min(cache_len, window) if window else cache_len
    from repro.models.layers import bf16
    sp = ParamSpec((batch, t, cfg.num_kv_heads, cfg.head_dim), bf16,
                   ("batch", "kv_seq", "kv_heads", None), init="zeros")
    return {"k": sp, "v": sp}
