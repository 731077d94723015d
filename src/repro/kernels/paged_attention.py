"""Pallas TPU kernel: decode attention over the vanilla paged KV layout.

The state-of-practice path the paper measures against.  K/V blocks are
scattered across a shared pool; the block table (scalar-prefetched so the
index map can chase it) drives a gather-style DMA per KV tile.  Same online-
softmax math as ``partition_attention`` — the layout indirection is the only
difference, which is exactly the HotMem-vs-vanilla contrast at kernel level.

Grid: (P, Hkv, MB) — one step per (request, kv head, table slot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -2.0 ** 30


def _kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, bt: int, n_b: int, cap: float, scale: float):
    pi = pl.program_id(0)
    bi = pl.program_id(2)

    @pl.when(bi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[pi]
    mapped = tab_ref[pi, bi] >= 0

    @pl.when(mapped)
    def _step():
        q = q_ref[0, 0]                                # (G, Dh)
        k = k_ref[0, :, 0, :]                          # (BT, Dh)
        v = v_ref[0, :, 0, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if cap:
            s = jnp.tanh(s / cap) * cap
        tok = bi * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        s = jnp.where(tok <= pos, s, NEG_INF)          # linear fill
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[...] = m_new

    @pl.when(bi == n_b - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, tables, positions, *,
                    logit_cap: float = 0.0, scale: float | None = None,
                    interpret: bool = True):
    """q (P, Hkv, G, Dh); k/v_pool (NB, BT, Hkv, Dh); tables (P, MB) int32
    (-1 = unmapped); positions (P,).  Returns (P, Hkv, G, Dh)."""
    p, hkv, g, dh = q.shape
    nb, bt = k_pool.shape[:2]
    mb = tables.shape[1]
    if scale is None:
        scale = dh ** -0.5

    kernel = functools.partial(_kernel, bt=bt, n_b=mb, cap=logit_cap,
                               scale=scale)

    def kv_index(pi, h, bi, tab, pos):
        return (jnp.maximum(tab[pi, bi], 0), 0, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                         # tables, positions
        grid=(p, hkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda pi, h, bi, tab, pos: (pi, h, 0, 0)),
            pl.BlockSpec((1, bt, 1, dh), kv_index),
            pl.BlockSpec((1, bt, 1, dh), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda pi, h, bi, tab, pos: (pi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), f32),
            pltpu.VMEM((g, 1), f32),
            pltpu.VMEM((g, dh), f32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32), q, k_pool,
      v_pool)
