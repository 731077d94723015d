"""Pallas TPU kernel: decode attention over contiguous HotMem partitions.

The HotMem fast path.  Each request's KV lives contiguously in its partition
row, so the kernel streams (BT, Dh) tiles of K/V straight from HBM into VMEM
with sequential DMAs — no gather, no block-table indirection (contrast with
``paged_attention``).  Online-softmax accumulation over KV tiles (flash
decoding); ring-cache masking for windowed layers.

Grid: (P, Hkv, T // BT) — partitions and KV heads parallel, KV tiles
sequential (accumulator in VMEM scratch).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -2.0 ** 30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bt: int, t: int, n_t: int, window: int, cap: float,
            scale: float):
    pi = pl.program_id(0)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                   # (G, Dh)
    k = k_ref[0, :, 0, :]                             # (BT, Dh)
    v = v_ref[0, :, 0, :]
    pos = pos_ref[pi]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32) * scale   # (G, BT)
    if cap:
        s = jnp.tanh(s / cap) * cap
    slots = ti * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
    gidx = pos - ((pos - slots) % t)                  # ring: global index
    valid = gidx >= 0
    if window:
        valid &= gidx > pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    m_ref[...] = m_new

    @pl.when(ti == n_t - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def partition_attention(q, k_cache, v_cache, positions, *, window: int = 0,
                        logit_cap: float = 0.0, scale: float | None = None,
                        block_t: int = 512, interpret: bool = True):
    """q (P, Hkv, G, Dh); k/v_cache (P, T, Hkv, Dh); positions (P,) int32.
    Returns (P, Hkv, G, Dh)."""
    p, hkv, g, dh = q.shape
    t = k_cache.shape[1]
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    n_t = t // bt
    if scale is None:
        scale = dh ** -0.5

    kernel = functools.partial(_kernel, bt=bt, t=t, n_t=n_t, window=window,
                               cap=logit_cap, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p, hkv, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda pi, h, ti, pos: (pi, h, 0, 0)),
            pl.BlockSpec((1, bt, 1, dh), lambda pi, h, ti, pos:
                         (pi, ti, h, 0)),
            pl.BlockSpec((1, bt, 1, dh), lambda pi, h, ti, pos:
                         (pi, ti, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda pi, h, ti, pos:
                               (pi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), f32),      # running max
            pltpu.VMEM((g, 1), f32),      # running denominator
            pltpu.VMEM((g, dh), f32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(positions.astype(jnp.int32), q, k_cache, v_cache)
