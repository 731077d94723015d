"""Pallas TPU kernel: KV block migration (compaction) — the cost HotMem
eliminates.

Copies ``count`` live blocks from the pool tail into free head slots before
a vanilla arena shrink: pool[dst[i]] <- pool[src[i]].  One grid step per
move streams a whole (BT, Hkv, Dh) block HBM->VMEM->HBM; the move list is
scalar-prefetched so both index maps chase it.  The pool is donated
(input/output aliased) so untouched blocks stay in place.

This is the TPU analogue of Linux page migration: its bytes scale with
occupancy, it burns HBM bandwidth, and it runs *between* decode steps —
the interference the paper's Fig. 7/10 measure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(src_ref, dst_ref, pool_ref, out_ref):
    del src_ref, dst_ref
    out_ref[...] = pool_ref[...]


def kv_compact(pool, src, dst, *, interpret: bool = True):
    """pool (NB, BT, Hkv, Dh); src/dst (M,) int32 move list (pad unused
    entries with src=dst so they degenerate to self-copies).
    Returns the compacted pool."""
    m = src.shape[0]
    nb = pool.shape[0]
    blk = (1,) + pool.shape[1:]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                         # src, dst
        grid=(m,),
        in_specs=[
            pl.BlockSpec(blk, lambda i, s, d: (s[i],) + (0,) *
                         (len(blk) - 1)),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, s, d: (d[i],) + (0,) *
                               (len(blk) - 1)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},   # pool (after 2 scalar args) -> out
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(src.astype(jnp.int32), dst.astype(jnp.int32), pool)
