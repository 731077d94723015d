"""Public jit'd wrappers over the Pallas kernels.

On TPU the Pallas path runs compiled (``interpret=False``); everywhere else
(this CPU container, unit tests) the same kernel body executes in interpret
mode, validated against the ``ref.py`` oracles.  ``impl="ref"`` selects the
pure-jnp oracle — the serving engine uses it for timed CPU benchmarks where
interpret-mode tracing overhead would drown the signal.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.kv_compact import kv_compact as _kv_compact_kernel
from repro.kernels.kv_snapshot import (
    snapshot_capture as _snapshot_capture_kernel,
    snapshot_restore as _snapshot_restore_kernel,
)
from repro.kernels.paged_attention import paged_attention as _paged_kernel
from repro.kernels.partition_attention import \
    partition_attention as _partition_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window", "logit_cap", "scale",
                                             "impl"))
def partition_attention(q, k_cache, v_cache, positions, *, window=0,
                        logit_cap=0.0, scale=None, impl="pallas"):
    if impl == "ref":
        return ref.partition_attention(q, k_cache, v_cache, positions,
                                       window=window, logit_cap=logit_cap,
                                       scale=scale)
    return _partition_kernel(q, k_cache, v_cache, positions, window=window,
                             logit_cap=logit_cap, scale=scale,
                             interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("logit_cap", "scale", "impl"))
def paged_attention(q, k_pool, v_pool, tables, positions, *, logit_cap=0.0,
                    scale=None, impl="pallas"):
    if impl == "ref":
        return ref.paged_attention(q, k_pool, v_pool, tables, positions,
                                   logit_cap=logit_cap, scale=scale)
    return _paged_kernel(q, k_pool, v_pool, tables, positions,
                         logit_cap=logit_cap, scale=scale,
                         interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("impl",))
def kv_compact(pool, src, dst, *, impl="pallas"):
    if impl == "ref":
        count = src.shape[0]
        return ref.kv_compact(pool, src, dst, count)
    return _kv_compact_kernel(pool, src, dst, interpret=not _on_tpu())


# Module-level jits: one dispatch cache shared by every engine instance, so
# the first TIMED snapshot in any engine reuses a compile paid session-wide
# (the engine additionally pre-warms per shape before its timed region).

@functools.partial(jax.jit, static_argnames=("layout", "impl"))
def kv_snapshot_capture(leaves, rows, *, layout, impl="pallas"):
    """All leaves x rows -> one (N, row_elems) staging blob, one launch."""
    leaves = tuple(leaves)
    if impl == "ref":
        return ref.snapshot_capture(leaves, rows, layout)
    return _snapshot_capture_kernel(leaves, rows, layout=layout,
                                    interpret=not _on_tpu())


@functools.partial(jax.jit, static_argnames=("layout", "impl"))
def kv_snapshot_restore(leaves, blob, rows, *, layout, impl="pallas"):
    """Inverse scatter: blob rows -> every leaf at ``rows``, one launch.
    Returns the new leaves tuple (kernel path aliases leaves in place on
    TPU, same discipline as ``kv_compact``)."""
    leaves = tuple(leaves)
    if impl == "ref":
        return tuple(ref.snapshot_restore(leaves, blob, rows, layout))
    return tuple(_snapshot_restore_kernel(leaves, blob, rows, layout=layout,
                                          interpret=not _on_tpu()))
