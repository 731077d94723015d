"""Pallas TPU kernels: fused snapshot gather-capture / scatter-restore.

The snapshot data plane used to pay one dispatch per cache leaf: capture
sliced every leaf of an arena row (``cache_read_row``) and ``device_get``
materialized each slice as its own transfer; restore ran one ``.at[].set``
per leaf.  These kernels collapse a whole row (or a batch of rows) into
ONE launch each:

  capture — grid step ``i`` DMAs row ``rows[i]`` of every leaf into that
            leaf's row stage; XLA concatenates the stages into a single
            contiguous staging blob ``(n_rows, row_elems)``.  The blob's
            byte image is exactly the leaf-order concatenation of each
            slice's C-order bytes — the same layout the engine's
            paginator hashes — so one ``device_get`` of the blob is the
            entire device->host cost and pagination never re-copies.
  restore — the inverse scatter: XLA carves the blob into row stages and
            grid step ``i`` DMAs each into its leaf at ``rows[i]``.  The
            leaves are aliased (``input_output_aliases``), so untouched
            rows stay in place — the same in-place discipline as
            ``kv_compact``.

Leaves and stages stay in HBM (``memory_space=pl.ANY``) and every copy is
a DMA, so nothing is staged in VMEM: a 56 MiB qwen2-1.5b row at 2048
tokens moves under the same kernel as a 4 KiB test row.  Rows are
scalar-prefetched so each DMA can address its row.

TPU tiling decides which leaves a DMA can move.  HBM tiles an array over
its two minor dims, and a DMA must cover whole tiles.  A KV leaf ``(G, B,
T, H, D)`` holds a row as whole ``(T, H, D)`` tiles (size-1 ``H`` dropped:
XLA already stores MQA KV as ``(G, B, 1, T, D)``).  A leaf whose batch
axis is one of the tiled dims — RG-LRU state ``(G, B, W)``, and the conv
histories ``(G, B, 3, W)`` that XLA stores as ``(G, 3, B, W)`` — holds a
row as one sublane per tile, half a 32-bit word in bf16: no DMA can move
it alone.  Those leaves are gathered / scattered by XLA in the same
executable (``_take_rows`` / ``_put_rows``).  Off-TPU the kernels run in
interpret mode; ``tests/test_tpu_compile.py`` compiles them for v5e at
published widths.

Roofline contract (the dace ``RooflineModel`` wrapper pattern: every
kernel gets an analytic model and measurements are checked against it):
``capture_cost``/``restore_cost`` predict the bytes each launch must move
from the *cache specs alone*; the device benchmark publishes expected vs
measured bytes per (shape x page size) cell and the ``BENCH_10.json``
gate fails if they ever drift apart by more than 2x.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import roofline


# ---------------------------------------------------------------------------
# Row layout: the flat byte image of one arena row
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One cache leaf's slice of the staging blob."""
    axis: int                    # leaf batch (row) axis
    block_shape: tuple           # leaf shape with the batch extent -> 1
    size: int                    # elements of one row slice
    offset: int                  # element offset into the blob row


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """Static description of a cache tree's per-row staging blob.

    Per-row slice shapes do not depend on the arena row count (only the
    batch extent varies), so one layout is valid across every bucket of
    the ladder.  Hashable -> usable as a jit static argument."""
    slots: tuple                 # tuple[LeafSlot, ...] in tree-flatten order
    dtype: str                   # shared leaf dtype (cache trees are bf16)
    total_elems: int

    @property
    def itemsize(self) -> int:
        import numpy as np
        return np.dtype(self.dtype).itemsize

    @property
    def row_bytes(self) -> int:
        return self.total_elems * self.itemsize

    def signature(self) -> tuple:
        """Shape/dtype fingerprint stored in snapshot payloads so a
        restore can assert the blob still matches the live cache tree."""
        return tuple((s.block_shape, self.dtype) for s in self.slots)


def build_layout(leaves: Sequence[Any], axes: Sequence[int]) -> RowLayout:
    """Layout from (leaf, batch_axis) pairs (arrays or tracers)."""
    assert len(leaves) == len(axes) and leaves
    dtypes = {str(x.dtype) for x in leaves}
    assert len(dtypes) == 1, \
        f"fused snapshot blob needs one leaf dtype, got {sorted(dtypes)}"
    slots, off = [], 0
    for x, ax in zip(leaves, axes):
        shape = tuple(x.shape)
        block = shape[:ax] + (1,) + shape[ax + 1:]
        size = math.prod(block)
        slots.append(LeafSlot(axis=ax, block_shape=block, size=size,
                              offset=off))
        off += size
    return RowLayout(slots=tuple(slots), dtype=dtypes.pop(),
                     total_elems=off)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _dma_view(shape: tuple, axis: int, itemsize: int):
    """The shape a DMA sees for a leaf whose arena row is a run of WHOLE
    tiles, or None when it is not.

    TPU HBM tiles an array over its two minor dims.  A row can be moved by
    DMA only when its batch axis lies outside them and the second-minor
    extent is a whole number of tiles: a multiple of 8 rows, or 1, 2 or 4
    rows that fill whole 32-bit words (bf16 packs two rows per word, so
    one bf16 row is half a word).  Size-1 dims other than the batch axis
    are dropped first: XLA already stores such a dim outside the tiled
    pair (MQA KV ``(G, B, T, 1, D)`` lives as ``(G, B, 1, T, D)``), so
    the reshape costs no copy.
    """
    keep = [d for d, n in enumerate(shape) if n != 1 or d == axis]
    view = tuple(shape[d] for d in keep)
    axis = keep.index(axis)
    if axis >= len(view) - 2:
        return None
    rows = view[-2]
    if rows % 8 and not (rows in (1, 2, 4) and rows * itemsize >= 4):
        return None
    return view, axis


def _dma_slots(layout: RowLayout, leaves) -> list:
    """Per slot: ``(view, view_axis)`` for DMA'd leaves, None otherwise."""
    return [_dma_view(tuple(x.shape), s.axis, layout.itemsize)
            for s, x in zip(layout.slots, leaves)]


def _row_block(view: tuple, axis: int) -> tuple:
    return view[:axis] + (1,) + view[axis + 1:]


def _take_rows(leaf, rows, slot: LeafSlot):
    """XLA gather of ``rows`` of one leaf: (N, slot.size)."""
    sl = jnp.moveaxis(jnp.take(leaf, rows, axis=slot.axis), slot.axis, 0)
    return sl.reshape(rows.shape[0], slot.size)


def _put_rows(leaf, chunk, rows, slot: LeafSlot):
    """XLA scatter of blob chunk (N, slot.size) into one leaf at ``rows``."""
    rest = slot.block_shape[:slot.axis] + slot.block_shape[slot.axis + 1:]
    vals = jnp.moveaxis(chunk.reshape((rows.shape[0],) + rest), 0,
                        slot.axis)
    idx = (slice(None),) * slot.axis + (rows,)
    return leaf.at[idx].set(vals.astype(leaf.dtype))


def _lanes(n: int, size: int) -> tuple:
    """Lane-dense shape ``(n, size // 128, 128)`` for blob pieces, when
    ``size`` allows it.  TPU XLA relayouts a many-dim row stage into a
    ``(n, size)`` matrix with a minor dim of millions very slowly (tens
    of seconds of compile per shape); the 3-D hop compiles in well under
    one second and leaves the same bytes."""
    return (n, size // 128, 128) if size % 128 == 0 else (n, size)


def _row_at(axis: int, r):
    return (slice(None),) * axis + (pl.ds(r, 1),)


def _dma_call(views, rows, srcs, out_shape, *, capture: bool,
              interpret: bool):
    """ONE ``pallas_call`` whose grid step ``i`` DMAs row ``rows[i]`` of
    every viewed leaf, HBM to HBM: leaf -> row stage (capture) or row
    stage -> aliased leaf (restore).  Nothing is staged in VMEM, so no
    block-shape rule or scoped-VMEM limit applies at any row size."""
    k = len(views)

    def kernel(rows_ref, *refs):
        i = pl.program_id(0)
        r = rows_ref[i]
        sems = refs[-1]
        copies = []
        for j, (_view, axis) in enumerate(views):
            if capture:
                src, dst = refs[j].at[_row_at(axis, r)], refs[k + j].at[i]
            else:
                src, dst = refs[j].at[i], refs[2 * k + j].at[_row_at(axis, r)]
            cp = pltpu.make_async_copy(src, dst, sems.at[j])
            cp.start()
            copies.append(cp)
        for cp in copies:
            cp.wait()

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows.shape[0],),
            in_specs=[hbm] * len(srcs),
            out_specs=[hbm] * k,
            scratch_shapes=[pltpu.SemaphoreType.DMA((k,))],
        ),
        out_shape=out_shape,
        # restore: leaf j (after 1 scalar arg + k row stages) aliases out j
        input_output_aliases={} if capture else
        {1 + k + j: j for j in range(k)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rows, *srcs)


def snapshot_capture(leaves, rows, *, layout: RowLayout,
                     interpret: bool = True):
    """Gather ``rows`` of every cache leaf into one staging blob.

    leaves: flat cache leaves (tree-flatten order of the cache tree);
    rows (N,) int32 arena row ids.  Returns ``(N, layout.total_elems)``
    in the shared leaf dtype.  Every leaf whose row is whole tiles moves
    in ONE kernel launch (``_dma_call``); a leaf whose row is a sublane
    inside each tile (RG-LRU state, conv history) is gathered by XLA in
    the same executable.  XLA then concatenates the per-leaf rows into
    the blob (a copy of the blob, never of the arena).
    """
    rows = rows.astype(jnp.int32)
    n = rows.shape[0]
    views = _dma_slots(layout, leaves)
    picked = [(v, x) for v, x in zip(views, leaves) if v is not None]
    stages = iter(_dma_call(
        [v for v, _ in picked], rows,
        [x.reshape(v[0]) for v, x in picked],
        [jax.ShapeDtypeStruct((n,) + _row_block(*v), x.dtype)
         for v, x in picked],
        capture=True, interpret=interpret) if picked else ())
    pieces = [_take_rows(x, rows, s) if v is None else next(stages)
              for v, x, s in zip(views, leaves, layout.slots)]
    if not all(s.size % 128 == 0 for s in layout.slots):
        return jnp.concatenate([p.reshape(n, -1) for p in pieces], axis=1)
    return jnp.concatenate([p.reshape(_lanes(n, s.size)) for p, s in
                            zip(pieces, layout.slots)], axis=1).reshape(n, -1)


def snapshot_restore(leaves, blob, rows, *, layout: RowLayout,
                     interpret: bool = True):
    """Scatter blob rows back into every cache leaf at ``rows`` — the
    exact inverse of ``snapshot_capture``: whole-tile leaves in ONE
    aliased kernel launch (untouched rows stay in place), the rest by
    XLA in the same executable.  Returns the new leaves.
    """
    rows = rows.astype(jnp.int32)
    n = rows.shape[0]
    views = _dma_slots(layout, leaves)
    if all(s.size % 128 == 0 for s in layout.slots):
        lanes = blob.reshape(_lanes(n, layout.total_elems))
        chunks = [lanes[:, s.offset // 128:(s.offset + s.size) // 128]
                  for s in layout.slots]
    else:
        chunks = [blob[:, s.offset:s.offset + s.size] for s in layout.slots]
    picked = [(v, x, c) for v, x, c in zip(views, leaves, chunks)
              if v is not None]
    outs = iter(_dma_call(
        [v for v, _, _ in picked], rows,
        [c.reshape((n,) + _row_block(*v)).astype(x.dtype)
         for v, x, c in picked] + [x.reshape(v[0]) for v, x, _ in picked],
        [jax.ShapeDtypeStruct(v[0], x.dtype) for v, x, _ in picked],
        capture=False, interpret=interpret) if picked else ())
    return [_put_rows(x, c, rows, s) if v is None
            else next(outs).reshape(x.shape)
            for v, x, c, s in zip(views, leaves, chunks, layout.slots)]


# ---------------------------------------------------------------------------
# Roofline bytes models (analytic — from specs, never from live arrays)
# ---------------------------------------------------------------------------


def expected_row_bytes(cfg, partition_tokens: int) -> int:
    """Bytes of one arena row's staging blob, derived from the cache
    SPECS (an independent code path from the live layout, so a silent
    layout change shows up as expected-vs-measured drift)."""
    import numpy as np
    from repro.models.model import cache_specs
    from repro.models.layers import tree_map_specs
    total = 0

    def acc(s):
        nonlocal total
        total += math.prod(s.shape) * np.dtype(s.dtype).itemsize

    tree_map_specs(acc, cache_specs(cfg, 1, partition_tokens))
    return total


def capture_cost(row_bytes: int, n_rows: int) -> dict[str, float]:
    """Bytes one fused capture launch must move: read every leaf slice,
    write the blob (HBM), then one device->host copy of the blob."""
    hbm = 2.0 * n_rows * row_bytes
    d2h = float(n_rows * row_bytes)
    return {"hbm_bytes": hbm, "host_bytes": d2h,
            "memory_s": hbm / roofline.HBM_BW}


def restore_cost(row_bytes: int, n_rows: int,
                 new_fraction: float = 1.0) -> dict[str, float]:
    """Bytes one fused restore moves: host->device only for the pages not
    already mapped (CoW), then blob read + leaf scatter write in HBM."""
    hbm = 2.0 * n_rows * row_bytes
    h2d = float(n_rows * row_bytes) * new_fraction
    return {"hbm_bytes": hbm, "host_bytes": h2d,
            "memory_s": hbm / roofline.HBM_BW}


# ---------------------------------------------------------------------------
# Data-plane accounting (dispatch / transfer counters the tests assert on)
# ---------------------------------------------------------------------------

STATS = {
    "capture_launches": 0,       # fused capture executions
    "restore_launches": 0,       # fused restore executions
    "d2h_transfers": 0,          # device->host copies (capture readout)
    "d2h_bytes": 0,
    "h2d_transfers": 0,          # host->device copies (restore staging)
    "h2d_bytes": 0,
    "remap_restores": 0,         # fully-mapped CoW restores (zero h2d)
}


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


def note_launch(kind: str) -> None:
    STATS[f"{kind}_launches"] += 1


def note_d2h(nbytes: int) -> None:
    STATS["d2h_transfers"] += 1
    STATS["d2h_bytes"] += int(nbytes)


def note_h2d(nbytes: int) -> None:
    STATS["h2d_transfers"] += 1
    STATS["h2d_bytes"] += int(nbytes)


def note_remap() -> None:
    STATS["remap_restores"] += 1
