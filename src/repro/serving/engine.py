"""Elastic serving engine: the FaaS-runtime analogue of paper §4.1.

One engine = one replica (VM).  Requests (function invocations) are admitted
into arena partitions, prefilled (cold start), batch-decoded (continuous
batching), kept warm for ``keep_alive`` (idle container pool), recycled, and
the arena is resized up/down a bucket ladder as demand moves (plug/unplug).

Start paths, fastest first (each leaves its own ``StepEvent``):
  warm_start — a kept-alive container's partition is re-bound by metadata
               adoption (zero data movement, zero wall);
  restore    — the host snapshot pool held the function's prefix KV (a warm
               container expired earlier and its partition was copied out
               instead of discarded); one host->device row write, no model
               compute;
  prefill    — cold start: full prompt forward pass.
When a warm container expires past keep-alive, its partition is offered
to the broker's snapshot pool first (``_offer_snapshot`` — a real device
readout, paid in bytes and wall) and only then released.  Warm-suffix
eviction under host pressure deliberately discards instead: at pressure
time a capture would either divert the open grant's units or be squeezed
right back (see ``_evict_warm_suffix``).

Timebase: a *virtual clock* advanced by the measured wall time of every
device operation (prefill, decode step, migration, zero-fill).  Arrivals are
virtual-time stamped, so trace-driven benchmarks measure real relative costs
(reclaim vs decode interference) without running 300 wall-clock seconds.

Host spans: every phase of a tick is a ``jax.profiler.TraceAnnotation``
named ``engine.<phase>``, so a profiled run shows it on the host plane of
the same trace as the device ops, and is stamped with ``time.perf_counter``
(see ``_tick``).  Each ``StepEvent`` carries the stamps of the work it
measured; a decode event carries the host self time of its tick's phases.
With the profiler off a span costs two clock reads and an inactive
annotation.

Modes (paper Fig. 8/9/10):
  hotmem  — partition arena; shrink is metadata + prefix slice.
  vanilla — same compute, but a physical paged twin of the KV leaves is
            maintained; shrink must first run real migration copies.
  static  — statically over-provisioned (never resizes).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.host import (AlwaysGrantBroker, Grant, MemoryBroker,
                                ReclaimOrder)
from repro.configs.base import ModelConfig
from repro.core.arena import ArenaSpec, ReclaimEvent
from repro.core.elastic import ElasticArena, bucket_ladder, target_bucket
from repro.kernels import kv_snapshot
from repro.models import model as M
from repro.serving.request import Request, State, slo_tier_of

i32 = jnp.int32


# ---------------------------------------------------------------------------
# Snapshot data plane: staged row blobs + content-addressed pagination.
# Pure host-side logic lives at module level so the fast tier can test it
# without booting an engine.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StagedRow:
    """Host-side snapshot payload: ONE contiguous byte buffer (the fused
    capture kernel's single ``device_get``) plus enough metadata to carve
    it back into a batch==1 cache tree of zero-copy views on demand."""
    blob: np.ndarray             # (row_bytes,) uint8
    treedef: Any                 # cache tree structure
    metas: tuple                 # ((row-slice shape, dtype str), ...)

    @property
    def nbytes(self) -> int:
        return int(self.blob.nbytes)

    def tree(self):
        return blob_to_row_tree(self.blob, self.treedef, self.metas)


def blob_to_row_tree(blob_u8: np.ndarray, treedef, metas):
    """Carve a staged row blob into a batch==1 cache tree of zero-copy
    ``np.frombuffer`` views — no bytes move; every leaf aliases the blob."""
    leaves, off = [], 0
    for shape, dtype in metas:
        dt = np.dtype(dtype)
        n = int(np.prod(shape))
        leaves.append(np.frombuffer(blob_u8, dtype=dt, count=n,
                                    offset=off).reshape(shape))
        off += n * dt.itemsize
    assert off == blob_u8.nbytes, (off, blob_u8.nbytes)
    return jax.tree.unflatten(treedef, leaves)


def paginate_blob(blob_u8, units: int, page_bytes: int,
                  n_dev: int = 1) -> list:
    """Split a staged row blob into fixed-size content-addressed pages.

    Each chunk is hashed in place (memoryview slices — the blob is never
    re-materialized as one bytes object) and keyed by content digest with
    the page's unit charge folded in, so one digest always means one
    (content, units) pair — the store asserts that.  Units spread over
    the pages in whole mesh stripes so ANY subset of pages charges
    balanced across devices; short manifests may carry zero-unit tail
    pages.  The digest formula is pinned: the fused blob's byte image
    equals the per-leaf ``tobytes()`` concatenation of the old path, so
    digests (and the dedup baselines keyed on them) are unchanged."""
    mv = memoryview(np.ascontiguousarray(blob_u8)).cast("B")
    chunks = [mv[i:i + page_bytes]
              for i in range(0, len(mv), page_bytes)] or [memoryview(b"")]
    assert units % n_dev == 0, (units, n_dev)
    base, rem = divmod(units // n_dev, len(chunks))
    specs = []
    for i, chunk in enumerate(chunks):
        u = (base + (1 if i < rem else 0)) * n_dev
        digest = "%s-%d" % (hashlib.sha256(chunk).hexdigest()[:16], u)
        specs.append((digest, u, len(chunk), bytes(chunk)))
    return specs


def assemble_pages(specs: list) -> np.ndarray:
    """Concatenate page payloads into ONE contiguous uint8 host buffer:
    each page is wrapped in a zero-copy ``np.frombuffer`` view and copied
    exactly once into its slot — the single host-side copy a paged
    restore pays before its one fused host->device transfer."""
    total = sum(b for _d, _u, b, _p in specs)
    out = np.empty(total, np.uint8)
    off = 0
    for _d, _u, b, p in specs:
        out[off:off + b] = np.frombuffer(p, np.uint8, count=b)
        off += b
    return out


@dataclasses.dataclass
class StepEvent:
    """One step of work in ``ServeEngine.events``.

    ``t`` is the engine's virtual time at the event's end; ``kind`` is one
    of ``decode``, ``prefill``, ``warm_start``, ``restore``, ``snapshot``,
    ``plug``, ``unplug`` or ``stall``; ``wall_s`` is what the virtual clock
    was charged for it.  ``start`` and ``end`` are ``time.perf_counter``
    stamps of the host work measured for it (the clock of the broker's
    order stamps); ``host_s`` is their difference.  ``modeled_s`` is the part of ``wall_s`` that no clock
    measured: for a restore, the copy-on-write discount on pages this
    replica already holds (negative) plus the modeled inter-host copy of a
    migrated entry, so ``wall_s == host_s + modeled_s``; 0 for every other
    kind.  A ``decode`` event's ``phases`` holds the host self time, in
    seconds, of each phase of the tick that ran it (see
    ``ServeEngine._tick``); ``launch + wait`` is its ``wall_s``."""
    t: float
    kind: str
    wall_s: float
    detail: dict[str, Any] = dataclasses.field(default_factory=dict)
    start: float = 0.0
    end: float = 0.0
    modeled_s: float = 0.0
    phases: Optional[dict[str, float]] = None

    @property
    def host_s(self) -> float:
        return self.end - self.start


def _covered_s(events, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` inside the measured extent of any event."""
    total, edge = 0.0, lo
    for a, b in sorted((ev.start, ev.end) for ev in events):
        a, b = max(a, edge), min(b, hi)
        if b > a:
            total += b - a
            edge = b
    return total


class _Phase:
    """Host span ``engine.<name>`` on the profiler's clock that records its
    self time as ``phases[key]``: its ``perf_counter`` wall less the
    measured extent of the step events appended inside it.  Entering
    returns the profiler annotation (``set_metadata`` adds arguments)."""
    __slots__ = ("events", "phases", "key", "span", "n0", "t0")

    def __init__(self, events: list, phases: dict, key: str,
                 name: Optional[str] = None):
        self.events, self.phases, self.key = events, phases, key
        self.span = jax.profiler.TraceAnnotation(f"engine.{name or key}")

    def __enter__(self):
        self.span.__enter__()
        self.n0 = len(self.events)
        self.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.phases[self.key] = t1 - self.t0 - _covered_s(
            self.events[self.n0:], self.t0, t1)
        return self.span.__exit__(*exc)


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, spec: ArenaSpec, *,
                 mode: str = "hotmem", keep_alive: float = 10.0,
                 headroom: int = 1, seed: int = 0, prewarm: bool = True,
                 broker: Optional[MemoryBroker] = None,
                 replica_id: str = "r0",
                 snapshot_page_bytes: Optional[int] = None,
                 snapshot_impl: Optional[str] = None):
        assert mode in ("hotmem", "vanilla", "static")
        assert snapshot_page_bytes is None or snapshot_page_bytes > 0
        assert snapshot_impl in (None, "pallas", "ref")
        if mode == "vanilla":
            assert cfg.family not in ("ssm", "hybrid"), \
                "paged baseline mirrors token-extensive KV only"
        self.cfg = cfg
        self.params = params
        self.spec = spec
        self.mode = mode
        self.keep_alive = keep_alive
        self.headroom = headroom
        self.ladder = bucket_ladder(spec.n_partitions,
                                    min_units=min(2, spec.n_partitions))
        start = spec.n_partitions if mode == "static" else self.ladder[0]
        # vanilla's model-facing row view stays full-size (compute reads
        # through block tables conceptually); its *physical* pool resizes
        rows = spec.n_partitions if mode in ("static", "vanilla") else start
        self.caches = M.init_caches(cfg, rows, spec.partition_tokens)
        # physical paged twin of token-extensive KV leaves (vanilla only);
        # for hotmem/static the arena is metadata-only and the engine owns
        # the device tree (one copy, donated through the decode step)
        self.pool = self._make_pool(start) if mode == "vanilla" else None
        self.arena = ElasticArena(cfg, spec, mode, caches=self.pool,
                                  seed=seed, grant=self._host_grant,
                                  release=self._host_release)
        if mode != "vanilla":
            # managers sized in partitions; ladder starts small
            self.arena.manager.plugged = start
            import heapq
            self.arena.manager._free = list(range(start))
            heapq.heapify(self.arena.manager._free)
        else:
            bpp = spec.blocks_per_partition
            self.arena.manager.pool_blocks = start * bpp
            self.arena.manager._free = list(range(start * bpp))
            self.arena.manager._rng.shuffle(self.arena.manager._free)

        # host control plane: growth is a *request* to the broker, never a
        # unilateral resize.  Standalone engines get an unmetered broker,
        # so single-replica behavior is byte-identical to pre-broker code.
        # Async pipeline state: reclaim orders this VM owes the host
        # (drained incrementally at tick boundaries) and open grants whose
        # pending fills we claim as victims drain.
        self.replica_id = replica_id
        self._reclaim_orders: deque[ReclaimOrder] = deque()
        self._open_grants: list[Grant] = []
        self.drain_parts_per_tick = 1
        self.broker = broker if broker is not None else AlwaysGrantBroker()
        # sharded hosts: the replica's KV stripes one shard per device of
        # the broker's mesh.  Partitions are the engine's native grow/
        # shrink granule, so each partition must stripe evenly over the
        # mesh — asserted at boot, not discovered mid-reclaim.
        topo = getattr(self.broker, "topology", None)
        self._n_dev = topo.n_devices if topo is not None else 1
        if self._n_dev > 1:
            assert spec.blocks_per_partition % self._n_dev == 0, \
                f"partition of {spec.blocks_per_partition} blocks does " \
                f"not stripe over {self._n_dev} devices"
            # vanilla plugs/unplugs single blocks, which cannot stripe
            assert mode != "vanilla", \
                "vanilla mode is incompatible with a sharded host"
        self.broker.register(
            replica_id, start * spec.blocks_per_partition,
            reclaim=self.reclaim_for_broker, load=self.load, mode=mode,
            order_sink=None if mode == "static" else self._enqueue_order,
            shards=self._n_dev)

        self.now = 0.0
        self.pending: deque[Request] = deque()
        self.active: dict[str, Request] = {}
        self.warm: dict[str, list[tuple[float, str, int]]] = {}
        self.done: list[Request] = []
        self.events: list[StepEvent] = []
        # authoritative start-path counters: which admission path actually
        # ran (the router's route-time picks are predictions, these are
        # outcomes — see Router's accounting note)
        self.cold_starts = 0
        self.warm_starts = 0
        self.restore_starts = 0
        self.remote_restore_starts = 0   # restores that paid an inter-host
        #                                  copy (fleet snapshot migration)
        self._prof_tokens: dict[str, int] = {}   # profile -> prompt tokens
        # content-addressed capture (``snapshot_page_bytes`` set): offered
        # partitions split into fixed-size pages keyed by content hash,
        # and ``_mapped`` remembers which page digests this replica has
        # already materialized (captured or restored) — a later restore
        # maps those copy-on-write instead of re-copying them
        self.snapshot_page_bytes = snapshot_page_bytes
        self._mapped: set[str] = set()
        # fused snapshot data plane: rows move as one staging blob through
        # one kernel launch (see repro.kernels.kv_snapshot).  Like the
        # other ops, the Pallas path runs compiled on TPU only; off-TPU
        # the engine times the pure-jnp ref twin (interpret-mode tracing
        # overhead would drown the wall signal) — bit-identical bytes
        # either way, pinned by tests/test_kernels.py.
        self.snapshot_impl = snapshot_impl or \
            ("pallas" if jax.default_backend() == "tpu" else "ref")
        self._snap_layout = None
        self._snap_warmed: set = set()
        # digest -> (device u8 blob, start, stop): where page bytes are
        # already resident ON DEVICE.  A fully-mapped local CoW restore
        # reassembles its row from these slices — zero h2d payload bytes.
        self._device_pages: dict[str, tuple] = {}
        self._row_req: dict[int, Request] = {}
        self._decode_jit: dict[int, Any] = {}       # rows -> compiled step
        self._prefill_jit: dict[int, Any] = {}      # prompt len -> compiled
        if prewarm and mode == "hotmem":
            # AOT bucket ladder (DESIGN.md §5.3): precompile the decode
            # executable for every arena size so bucket switches are
            # metadata + slice, never a recompile
            for rows_n in self.ladder:
                self._warm_decode(rows_n)

    def _warm_decode(self, rows_n: int) -> None:
        if rows_n in self._decode_jit:
            return
        cfg = self.cfg

        def decode_step(params, toks, pos, caches):
            return M.decode_step(cfg, params, toks, pos, caches)
        # named, so the executable is ``jit_decode_step`` at every size
        self._decode_jit[rows_n] = jax.jit(decode_step, donate_argnums=(3,))
        caches = M.init_caches(self.cfg, rows_n, self.spec.partition_tokens)
        toks = jnp.zeros((rows_n, 1), i32)
        pos = jnp.zeros((rows_n,), i32)
        out, _ = self._decode_jit[rows_n](self.params, toks, pos, caches)
        jax.block_until_ready(out)

    # ------------------------------------------------------------ plumbing
    def _host_grant(self, native: int) -> int:
        """Arena host gate: convert this replica's native units (partitions
        for hotmem, blocks for vanilla) to broker blocks, request a grant,
        and floor the immediate portion back to native granularity.  A sync
        broker may steal inline — that victim-side reclaim wall is charged
        to *our* clock too (we serialized behind it); an async broker
        leaves the deficit pending on the grant instead, and the fills are
        claimed at later ticks while our decode proceeds."""
        bpp = self.spec.blocks_per_partition
        want = native if self.mode == "vanilla" else native * bpp
        t0 = time.perf_counter()
        g = self.broker.request_grant(self.replica_id, want)
        if g.stall_seconds:
            self.now += g.stall_seconds
            self.events.append(StepEvent(self.now, "stall", g.stall_seconds,
                                         {"units": g.granted}, start=t0,
                                         end=time.perf_counter()))
        if not g.done:
            self._open_grants.append(g)
        got = g.granted
        if self.mode == "vanilla":
            return got
        rem = got % bpp
        if rem:                           # sub-partition remainder: no use
            self.broker.release_units(self.replica_id, rem)
        return got // bpp

    def _enqueue_order(self, order: ReclaimOrder) -> None:
        """Order sink the broker calls under pressure: queue the shrink,
        to be drained incrementally at our own tick boundaries."""
        self._reclaim_orders.append(order)

    def _host_release(self, native: int) -> None:
        self.broker.release_units(
            self.replica_id,
            native if self.mode == "vanilla" else
            native * self.spec.blocks_per_partition)

    def _make_pool(self, parts: int):
        """Physical paged twin: every token-extensive leaf becomes a flat
        (NB, block_tokens, ...) block pool — one manager block id maps to
        the same token range across all layers, exactly the paper's
        whole-memory-block semantics.  Non-token leaves are skipped."""
        bt = self.spec.block_tokens
        t_part = self.spec.partition_tokens
        pools = []

        def to_pool(x, ax):
            tok_ax = ax + 1
            if x.ndim <= tok_ax or x.shape[tok_ax] != t_part:
                return
            if ax == 1:                       # (G, B, T, ...) -> (B, T, G...)
                x = jnp.moveaxis(x, 0, 2)[ :parts]
            else:
                x = x[:parts]
            nb = parts * (t_part // bt)
            pools.append(x.reshape((nb, bt) + x.shape[2:]))

        M.cache_axis_map(self.caches, to_pool)
        return pools or None

    def _rows(self) -> int:
        return M.cache_num_rows(self.caches)

    def _units(self) -> int:
        return self.arena.units() if self.mode != "vanilla" else \
            self.arena.units() // self.spec.blocks_per_partition

    # ------------------------------------------------------------- submit
    def submit(self, req: Request) -> None:
        self._prof_tokens[req.profile.name] = req.profile.prompt_tokens
        self.pending.append(req)

    # -------------------------------------------------------------- admit
    def _try_admit(self) -> tuple[int, int]:
        """Start every due pending request that finds a row; returns how
        many pending requests were scanned and how many were admitted."""
        still = deque()
        scanned = len(self.pending)
        while self.pending:
            req = self.pending.popleft()
            if req.submit_s > self.now:
                still.append(req)
                continue
            # batch-tier traffic is deliberately started cold: it must not
            # consume a warm container or a pooled snapshot — both are the
            # tight tier's tail-latency capacity (the slo_tiered policy's
            # engine-side half; "standard" is the default and unchanged)
            batch = slo_tier_of(req) == "batch"
            warm = None if batch else self.warm.get(req.profile.name)
            if warm:
                _, old_rid, row = warm.pop()
                with jax.profiler.TraceAnnotation("engine.warm", rid=req.rid):
                    self._start_warm(req, old_rid, row)
                continue
            got = self.arena.admit(req.rid)
            if got is None:
                still.append(req)
                continue
            row = got if self.mode != "vanilla" else self._alloc_row(req)
            if row is None:
                still.append(req)
                continue
            # probe restore feasibility first (no accounting): the pool's
            # hit / miss counters track restore fetches, not cold
            # admissions, and a payload-less entry must not be
            # MRU-refreshed by a lookup it can never serve
            snap = self.broker.snapshot_lookup(req.profile.name) \
                if self.mode == "hotmem" and not batch \
                and self.broker.snapshot_restorable(req.profile.name) \
                else None
            if snap is not None:
                with jax.profiler.TraceAnnotation("engine.restore",
                                                  rid=req.rid):
                    self._start_restore(req, row, snap)
            else:
                with jax.profiler.TraceAnnotation("engine.prefill",
                                                  rid=req.rid):
                    self._start_cold(req, row)
        self.pending = still
        return scanned, scanned - len(still)

    def _alloc_row(self, req) -> Optional[int]:
        used = set(self._row_req)
        for entries in self.warm.values():          # warm rows stay reserved
            used.update(row for _, _, row in entries)
        for r in range(self._rows()):
            if r not in used:
                return r
        return None

    def _activate(self, req: Request, row: int) -> None:
        """Shared tail of every start path (cold / warm / restore): the
        prompt KV is resident in ``row``, bind the request and enter the
        decode loop."""
        prof = req.profile
        self.arena.on_tokens(req.rid, prof.prompt_tokens)
        req.position = prof.prompt_tokens
        req.target_tokens = prof.prompt_tokens + prof.decode_tokens
        req.state = State.RUNNING
        self._row_req[row] = req
        self.active[req.rid] = req

    def _start_cold(self, req: Request, row: int) -> None:
        req.partition = row
        req.admitted_s = self.now
        req.state = State.PREFILL
        prof = req.profile
        prompt = np.full((1, prof.prompt_tokens),
                         hash(prof.name) % 97 + 1, np.int32)
        n = prof.prompt_tokens
        if n not in self._prefill_jit:
            def prefill(params, toks, row_caches):
                return M.prefill(self.cfg, params, {"tokens": toks},
                                 row_caches)[1]
            self._prefill_jit[n] = jax.jit(prefill, donate_argnums=(2,))
        t0 = time.perf_counter()
        row_caches = M.init_caches(self.cfg, 1, self.spec.partition_tokens)
        row_caches = self._prefill_jit[n](self.params, jnp.asarray(prompt),
                                          row_caches)
        self.caches = M.cache_write_row(self.caches, row_caches, row)
        jax.block_until_ready(jax.tree.leaves(self.caches)[0])
        t1 = time.perf_counter()
        wall = t1 - t0
        self.now += wall
        self.events.append(StepEvent(self.now, "prefill", wall,
                                     {"rid": req.rid}, start=t0, end=t1))
        self._activate(req, row)
        self.cold_starts += 1

    def _start_warm(self, req: Request, old_rid: str, row: int) -> None:
        """Warm start: prompt KV still resident in the partition — skip
        prefill entirely (the paper's warm-container fast path).  The
        partition is re-bound by metadata adoption, zero data movement."""
        req.partition = row
        req.admitted_s = self.now
        self.arena.manager.adopt(old_rid, req.rid)
        self._activate(req, row)
        self.warm_starts += 1
        t = time.perf_counter()
        self.events.append(StepEvent(self.now, "warm_start", 0.0,
                                     {"rid": req.rid, "row": row},
                                     start=t, end=t))

    def _start_restore(self, req: Request, row: int, snap) -> None:
        """Snapshot restore: the function's prefix KV was persisted to the
        host pool when its last warm container was recycled; copy it back
        into the freshly admitted partition.  No prefill forward pass —
        one host->device row write — so it is far cheaper than a cold
        start but, unlike warm adoption, pays real copy bytes.

        Source tagging: an entry the fleet migrated from another host
        still owes its modeled inter-host transfer wall
        (``Snapshot.copy_seconds``); the FIRST restore claims it — the
        event is tagged ``source="remote"`` with the origin host and the
        copy charge, and lands between a local restore and a cold
        prefill.  The entry is local thereafter (later restores tag
        ``source="local"``).

        Content-addressed entries restore COPY-ON-WRITE: pages whose
        digest this replica already materialized (an earlier capture or
        restore) are remapped, not re-copied — the charged wall scales by
        the fraction of pages actually new here, and the event reports
        ``pages_total`` / ``pages_shared``.  When EVERY page of a local
        entry is still resident on device (``_device_pages``), the row is
        reassembled from those mapped slices and scattered in place: the
        payload never leaves the device (zero host->device bytes)."""
        req.partition = row
        req.admitted_s = self.now
        req.state = State.PREFILL
        copy_s = snap.claim_copy() if hasattr(snap, "claim_copy") else 0.0
        specs = self.broker.snapshot_page_specs(snap.key) \
            if getattr(snap, "pages", None) is not None else None
        staged = isinstance(snap.payload, StagedRow)
        layout = remap = None
        if specs is not None or staged:
            layout = self._snapshot_layout()
            self._warm_snapshot_op("restore")
            remap = specs is not None and copy_s == 0.0 and \
                all(d in self._device_pages for d, _u, _b, _p in specs)
        t0 = time.perf_counter()
        if specs is None and not staged:
            # legacy opaque tree payload: per-leaf transfer + row write
            row_caches = jax.tree.map(jnp.asarray, snap.payload)
            self.caches = M.cache_write_row(self.caches, row_caches, row)
        else:
            if remap:
                # fully-mapped local CoW restore: concatenate the mapped
                # on-device byte slices back into a staging blob — no
                # payload byte crosses the host/device boundary
                parts = [dev[s:e] for dev, s, e in
                         (self._device_pages[d] for d, _u, _b, _p in specs)]
                dev_u8 = parts[0] if len(parts) == 1 else \
                    jnp.concatenate(parts)
                dev_blob = jax.lax.bitcast_convert_type(
                    dev_u8.reshape(1, layout.total_elems, layout.itemsize),
                    jnp.dtype(layout.dtype))
                kv_snapshot.note_remap()
            else:
                blob_u8 = self._reassemble(snap.payload, specs) \
                    if specs is not None else snap.payload.blob
                host_blob = blob_u8.view(np.dtype(layout.dtype)).reshape(
                    1, layout.total_elems)
                dev_blob = jnp.asarray(host_blob)   # ONE fused h2d copy
                kv_snapshot.note_h2d(host_blob.nbytes)
            self.caches = M.cache_write_rows(
                self.caches, dev_blob, jnp.asarray([row], i32),
                layout=layout, impl=self.snapshot_impl)
            kv_snapshot.note_launch("restore")
        jax.block_until_ready(jax.tree.leaves(self.caches)[0])
        t1 = time.perf_counter()
        wall = t1 - t0
        detail = {"rid": req.rid, "key": snap.key, "bytes": snap.nbytes,
                  "row": row, "source": "remote" if copy_s else "local"}
        if specs is not None:
            total = len(specs)
            shared = sum(1 for d, _u, _b, _p in specs if d in self._mapped)
            # CoW: only the new pages pay the host->device copy; shared
            # pages are a mapping (the measured wall is the full row
            # write, so the charge scales it by the new-page fraction:
            # a modeled discount, kept apart in ``modeled_s``)
            wall *= (total - shared) / total if total else 1.0
            self._mapped.update(d for d, _u, _b, _p in specs)
            self._index_device_pages(dev_blob, specs)
            detail["pages_total"] = total
            detail["pages_shared"] = shared
        wall += copy_s
        self.now += wall
        if copy_s:
            detail["origin"] = snap.origin_host
            detail["copy_s"] = copy_s
            self.remote_restore_starts += 1
        self.events.append(StepEvent(self.now, "restore", wall, detail,
                                     start=t0, end=t1,
                                     modeled_s=wall - (t1 - t0)))
        self._activate(req, row)
        self.restore_starts += 1

    # -------------------------------------------------------------- decode
    def _decode(self, phases: Optional[dict] = None) -> None:
        """One decode step over every arena row, then the per-row
        bookkeeping.  Host spans ``engine.decode`` (tiled by ``.inputs``,
        ``.launch`` up to the return of the jitted call, and ``.wait``
        for its result) and ``engine.decode.post``; their self times go
        into ``phases`` and the step's event."""
        phases = {} if phases is None else phases
        rows = self._rows()
        if rows not in self._decode_jit:
            self._warm_decode(rows)
        with jax.profiler.TraceAnnotation("engine.decode"):
            t_in = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode.inputs"):
                toks = np.zeros((rows, 1), np.int32)
                pos = np.zeros((rows,), np.int32)
                for row, req in self._row_req.items():
                    assert row < rows, \
                        f"active request {req.rid} bound to row {row} but " \
                        f"the arena holds only {rows} rows — a shrink " \
                        f"dropped a live row (free-suffix invariant " \
                        f"violated)"
                    pos[row] = req.position
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode.launch"):
                logits, self.caches = self._decode_jit[rows](
                    self.params, jnp.asarray(toks), jnp.asarray(pos),
                    self.caches)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.decode.wait"):
                jax.block_until_ready(logits)
            t2 = time.perf_counter()
        phases.update(inputs=t0 - t_in, launch=t1 - t0, wait=t2 - t1)
        wall = t2 - t0
        self.now += wall
        self.events.append(StepEvent(self.now, "decode", wall,
                                     {"batch": len(self._row_req)},
                                     start=t0, end=t2, phases=phases))
        with _Phase(self.events, phases, "post", "decode.post"):
            finished = []
            for row, req in list(self._row_req.items()):
                req.position += 1
                if req.first_token_s is None:
                    req.first_token_s = self.now
                if not self.arena.on_tokens(req.rid, 1):
                    req.state = State.KILLED
                    finished.append((row, req))
                    continue
                if req.position >= req.target_tokens:
                    req.state = State.DONE
                    finished.append((row, req))
            for row, req in finished:
                req.done_s = self.now
                self.done.append(req)
                del self.active[req.rid]
                del self._row_req[row]
                if req.state is State.DONE:
                    # to warm pool: the partition STAYS BOUND (idle
                    # container) until keep-alive expiry recycles it
                    self.warm.setdefault(req.profile.name, []).append(
                        (self.now, req.rid, row))
                # KILLED was already force-released by the manager

    # ------------------------------------------------------------- elastic
    def _snapshot_layout(self):
        """Static blob layout of one arena row (row-slice shapes do not
        depend on the arena's row count, so one layout survives every
        bucket switch)."""
        if self._snap_layout is None:
            self._snap_layout = M.cache_row_layout(self.caches)
        return self._snap_layout

    def _warm_snapshot_op(self, kind: str) -> None:
        """Dummy-execute the fused snapshot op for the CURRENT arena shape
        outside any timed region, so the first timed capture / restore
        measures data movement, not a jit compile (the snapshot twin of
        ``_warm_decode``'s AOT discipline).  The restore dummy's output is
        discarded — the op does not donate, so ``self.caches`` is
        untouched."""
        key = (kind, self._rows(), self.snapshot_impl)
        if key in self._snap_warmed:
            return
        layout = self._snapshot_layout()
        rows = jnp.zeros((1,), i32)
        if kind == "capture":
            out = M.cache_read_rows(self.caches, rows, layout=layout,
                                    impl=self.snapshot_impl)
        else:
            blob = jnp.zeros((1, layout.total_elems), layout.dtype)
            out = M.cache_write_rows(self.caches, blob, rows, layout=layout,
                                     impl=self.snapshot_impl)
        jax.block_until_ready(out)
        self._snap_warmed.add(key)

    def _index_device_pages(self, dev_blob, specs: list) -> None:
        """Remember where each page's bytes live ON DEVICE (byte slices of
        the staged blob): a later fully-mapped local CoW restore
        reassembles its row from these slices and never pays a
        host->device payload transfer."""
        dev_u8 = jax.lax.bitcast_convert_type(
            dev_blob, jnp.uint8).reshape(-1)
        off = 0
        for d, _u, b, _p in specs:
            self._device_pages[d] = (dev_u8, off, off + b)
            off += b

    def _offer_snapshot(self, prof_name: str, rid: str, row: int) -> bool:
        """Persist an about-to-be-recycled warm partition to the host
        snapshot pool instead of discarding its prefix KV.  The readout is
        ONE fused gather launch (every leaf's row slice lands in a single
        contiguous staging blob, ``kv_snapshot``) plus ONE device->host
        copy of that blob, charged to this replica's clock — paid only
        when the broker has room (brokers without a pool decline for
        free, keeping the discard path byte-identical to pre-snapshot
        behavior).  ``nbytes`` and pagination both read the same staged
        blob: the old path's double byte-materialization (tree traversal
        for nbytes, then per-leaf ``tobytes()`` again) is gone.

        With ``snapshot_page_bytes`` set the blob is split into
        content-addressed pages (``_paginate``) before the put, so the
        pool charges only pages its store does not already hold.  The
        room probe stays the conservative all-pages-new check — it runs
        BEFORE the device readout, when the page digests do not exist
        yet, so it must not depend on them."""
        if self.mode != "hotmem":
            return False            # prefix-KV rows are a hotmem concept
        units = self.spec.blocks_per_partition
        if not self.broker.snapshot_room(prof_name, units):
            return False
        layout = self._snapshot_layout()
        self._warm_snapshot_op("capture")
        t0 = time.perf_counter()
        dev_blob = M.cache_read_rows(self.caches, jnp.asarray([row], i32),
                                     layout=layout, impl=self.snapshot_impl)
        host = np.asarray(jax.device_get(dev_blob))
        t1 = time.perf_counter()
        wall = t1 - t0
        kv_snapshot.note_launch("capture")
        kv_snapshot.note_d2h(host.nbytes)
        blob_u8 = host.view(np.uint8).reshape(-1)    # zero-copy byte image
        nbytes = blob_u8.nbytes                      # == sum of leaf nbytes
        treedef = jax.tree.structure(self.caches)
        metas = tuple((s.block_shape, layout.dtype) for s in layout.slots)
        pages = None
        if self.snapshot_page_bytes is not None:
            payload: Any = ("paged", treedef, metas)
            pages = self._paginate(blob_u8, units)
        else:
            payload = StagedRow(blob=blob_u8, treedef=treedef, metas=metas)
        ok = self.broker.snapshot_put(
            prof_name, units=units, payload=payload,
            tokens=self._prof_tokens.get(prof_name, 0), nbytes=nbytes,
            replica_id=self.replica_id, pages=pages)
        if ok:
            if pages is not None:
                self._mapped.update(d for d, _u, _b, _p in pages)
                self._index_device_pages(dev_blob, pages)
            self.now += wall
            self.events.append(StepEvent(self.now, "snapshot", wall,
                                         {"key": prof_name, "rid": rid,
                                          "bytes": nbytes, "row": row},
                                         start=t0, end=t1))
        return ok

    def _paginate(self, blob_u8: np.ndarray, units: int) -> list:
        """Content-addressed pagination of the staged row blob (module-
        level ``paginate_blob`` does the work — pure host logic, fast-tier
        testable).  Digests are pinned across the kernel migration: the
        fused blob's byte image equals the per-leaf era's ``tobytes()``
        concatenation."""
        return paginate_blob(blob_u8, units, self.snapshot_page_bytes,
                             self._n_dev)

    def _reassemble(self, payload, specs: list) -> np.ndarray:
        """Rebuild the staged row blob from a paged entry: ONE contiguous
        host buffer assembled from zero-copy page views
        (``assemble_pages``).  Carving back into leaves happens on device
        in the single fused scatter-restore launch — not per leaf, and
        not on the host."""
        kind, _treedef, metas = payload
        assert kind == "paged", kind
        blob = assemble_pages(specs)
        want = sum(int(np.prod(s)) * np.dtype(d).itemsize for s, d in metas)
        assert blob.nbytes == want, (blob.nbytes, want)
        return blob

    def _recycle_idle(self) -> None:
        """Recycle idle containers past keep-alive: release their
        partitions/blocks (this is what makes memory reclaimable).  Each
        expiring container's partition is first offered to the host
        snapshot pool (warm-restart state outliving the container)."""
        for prof, entries in list(self.warm.items()):
            fresh = [e for e in entries
                     if self.now - e[0] < self.keep_alive]
            expired = [e for e in entries
                       if self.now - e[0] >= self.keep_alive]
            if expired and not self._reclaim_orders \
                    and not self.broker.snapshot_restorable(prof):
                # capture at most ONE expiring container per profile (the
                # pool keys by profile — same-key replacement would throw
                # away all but the last readout anyway), skip entirely
                # when the pool already holds a restorable copy (per-
                # profile KV is deterministic, so a re-capture would
                # same-key-replace byte-identical content at the cost of
                # a full device readout), and never mid-order-drain: the
                # readout wall would lengthen the very drain the
                # requester is waiting on, and the next pressured grant
                # would squeeze the snapshot right back
                t, rid, row = max(expired)       # newest expiring entry
                with jax.profiler.TraceAnnotation("engine.snapshot",
                                                  rid=rid):
                    self._offer_snapshot(prof, rid, row)
            for (_, rid, _row) in expired:
                self.arena.finish(rid)
            self.warm[prof] = fresh

    def _resize(self) -> None:
        if self.mode == "static":
            return
        demand = len(self.active) + sum(map(len, self.warm.values())) \
            + len(self.pending) + self.headroom
        tgt = target_bucket(self.ladder, max(demand, self.ladder[0]))
        cur = self._units()
        if tgt > cur:
            if self._reclaim_orders:
                # the host ordered this VM to shrink; plugging now would
                # ping-pong the same units back and forth between replicas
                return
            # growth is a plug *request* through the arena's host gate: the
            # broker may grant less than asked (and may first steal from an
            # idler replica to cover it), so size the row sync to what the
            # arena actually got.  Units already in flight on open grants
            # (pending on victims' orders, or escrowed awaiting our claim)
            # must not be re-requested.
            # grants account in broker blocks; tgt/cur are partitions
            owed = sum(g.pending + g.available for g in self._open_grants) \
                // self.spec.blocks_per_partition
            k = tgt - cur - owed
            if k <= 0:
                return
            units = k if self.mode != "vanilla" else \
                k * self.spec.blocks_per_partition
            self._grow_and_sync(units, via_gate=True)
        elif tgt < cur:
            k = cur - tgt
            if self.mode == "hotmem" and \
                    not self.arena.manager.shrink_plan(k):
                return                       # nothing reclaimable yet
            units = k if self.mode != "vanilla" else \
                k * self.spec.blocks_per_partition
            self._unplug_now(units)

    def _grow_and_sync(self, native: int, *, via_gate: bool,
                       detail: Optional[dict] = None) -> int:
        """Grow the arena (through the host gate, or absorbing an
        already-claimed grant fill) + row sync + virtual-clock charge +
        event log — the one plug protocol both growth paths share (the
        bit-identical-trace regression depends on it staying identical).
        Returns native units actually added."""
        with jax.profiler.TraceAnnotation("engine.plug"):
            start = time.perf_counter()
            before = self.arena.units()
            # absorb path: the claimed fill is a whole stripe (claim_grant
            # only releases coherent units); hotmem's native unit is a
            # partition, which stripes wholly, so only vanilla's block
            # granules need the stripe check — and vanilla is asserted off
            # for sharded hosts.
            wall = self.arena.plug(native) if via_gate \
                else self.arena.absorb(
                    native,
                    shards=self._n_dev if self.mode == "vanilla" else 1)
            added = self.arena.units() - before
            if added:
                t0 = time.perf_counter()
                self._sync_rows(self._units())
                jax.block_until_ready(jax.tree.leaves(self.caches)[0])
                t1 = time.perf_counter()
                wall += t1 - t0
                self.now += wall
                self.events.append(StepEvent(
                    self.now, "plug", wall,
                    {"units": added, **(detail or {})}, start=start, end=t1))
        return added

    def _unplug_now(self, units: int, *, stolen: bool = False
                    ) -> ReclaimEvent:
        """Unplug + row sync + virtual-clock charge + event log — shared by
        self-initiated shrink and broker-initiated steals (which do their
        own host accounting, hence ``notify_host=False``)."""
        with jax.profiler.TraceAnnotation("engine.unplug"):
            start = time.perf_counter()
            ev = self.arena.unplug(units, notify_host=not stolen)
            t0 = time.perf_counter()
            self._sync_rows(self._units())
            jax.block_until_ready(jax.tree.leaves(self.caches)[0])
            t1 = time.perf_counter()
            ev.wall_seconds += t1 - t0
        self.now += ev.wall_seconds
        detail = {"reclaimed_bytes": ev.reclaimed_bytes,
                  "migrated_bytes": ev.migrated_bytes}
        if stolen:
            detail["stolen"] = True
        self.events.append(StepEvent(self.now, "unplug", ev.wall_seconds,
                                     detail, start=start, end=t1))
        return ev

    def _sync_rows(self, parts: int) -> None:
        """Match the model-facing row cache to the arena partition count."""
        if self.mode == "vanilla":
            return
        rows = self._rows()
        if parts == rows:
            return
        if parts > rows:
            self.caches = M.cache_grow_rows(self.caches, parts)
        else:
            self.caches = M.cache_slice_rows(self.caches, parts)

    # ------------------------------------------------------- broker victim
    def load(self) -> int:
        """In-flight + queued invocations (the broker's idleness signal)."""
        return len(self.active) + len(self.pending)

    def _free_units(self) -> int:
        if self.mode == "vanilla":
            return self.arena.manager.free_blocks \
                // self.spec.blocks_per_partition
        return self.arena.manager.free_partitions

    def _evict_warm_suffix(self, k_parts: int) -> None:
        """HotMem shrink drops only a *free suffix* of the arena: extend
        that suffix by recycling warm (idle) containers sitting on its
        high rows, stopping at the first active row — killing anything
        below it cannot help and would waste warm-start state."""
        mgr = self.arena.manager
        warm_rows = {row: (t, prof, rid)
                     for prof, es in self.warm.items()
                     for (t, rid, row) in es}
        free = set(mgr._free)
        need, p = k_parts, mgr.plugged - 1
        while p >= 0 and need > 0:
            if p in free:
                need -= 1
            elif p in warm_rows:
                t, prof, rid = warm_rows[p]
                # deliberately NO snapshot capture here: warm-suffix
                # eviction only ever runs under host pressure (sync
                # inline steal or async order drain), where a capture
                # would either divert the open grant's own units (sync —
                # the broker fences the pool via _inline_reclaim) or
                # lengthen the drain the requester is waiting on and be
                # squeezed right back by the next pressured grant (pure
                # churn).  Capture rides the keep-alive expiry path
                # (_recycle_idle), which runs outside pressure.
                self.arena.finish(rid)
                self.warm[prof].remove((t, rid, p))
                need -= 1
            else:
                break                      # active row blocks the suffix
            p -= 1

    # -------------------------------------------------- async host pipeline
    def host_work(self) -> bool:
        """Open async-pipeline work: reclaim orders to drain (as victim) or
        grant fills to claim (as requester).  ``ClusterSim`` keeps ticking
        a replica while this is true so the pipeline always advances."""
        return bool(self._reclaim_orders) or bool(self._open_grants)

    def _service_reclaim_orders(self) -> None:
        """Drain the pending-unplug queue incrementally: at most
        ``drain_parts_per_tick`` partitions per tick, fencing high rows via
        ``_evict_warm_suffix`` before each partial unplug — so the victim's
        reclaim overlaps the requester's decode instead of stalling it
        (the async pipeline's victim side)."""
        q = self._reclaim_orders
        while q and not q[0].open:
            q.popleft()                  # filled naturally or canceled
        if not q:
            return
        order = q[0]
        chunk = min(self.drain_parts_per_tick
                    * self.spec.blocks_per_partition, order.remaining)
        freed, ev = self.reclaim_for_broker(chunk)
        if freed:
            shards = getattr(order, "shards", 1)
            if shards > 1:
                # sharded host: each freed partition stripes one slab per
                # device, so the fill lands shard-by-shard (the broker only
                # unfences the requester once every shard of the stripe is
                # home — coherent_filled, not filled).
                per = freed // shards
                accepted = sum(
                    self.broker.fulfill_order(order.order_id, per,
                                              ev if d == 0 else None,
                                              shard=d)
                    for d in range(shards))
            else:
                accepted = self.broker.fulfill_order(order.order_id,
                                                     freed, ev)
            if freed > accepted:         # rounding excess: normal release
                self.broker.release_units(self.replica_id, freed - accepted)
            if not order.open:
                q.popleft()
        elif not self.active and not self.pending \
                and not any(self.warm.values()):
            # fully drained VM with nothing left to give: abandon the rest
            # (a victim that finished naturally already filled the order
            # through release routing — this cancel is the leftover)
            self.broker.cancel_order(order.order_id)
            q.popleft()

    def _claim_grants(self, abandon: bool = False) -> None:
        """Requester side of the async pipeline: absorb units that reclaim
        orders drained into our open grants since the last tick (grant
        completion at our own tick boundary, where row growth is legal).
        With ``abandon`` (the engine is fully idle: its demand vanished),
        pending remainders are canceled so victims stop draining for us
        and a standalone ``run`` can terminate."""
        if not self._open_grants:
            return
        bpp = self.spec.blocks_per_partition
        for g in list(self._open_grants):
            got = self.broker.claim_grant(g)
            if got:
                if self.mode != "vanilla" and got % bpp:
                    self.broker.release_units(self.replica_id, got % bpp)
                native = got if self.mode == "vanilla" else got // bpp
                self._grow_and_sync(native, via_gate=False,
                                    detail={"async_fill": True})
            if abandon and not g.done:
                self.broker.abandon_grant(g)
            if g.done and g.available == 0 \
                    and getattr(g, "incoherent", 0) == 0:
                self._open_grants.remove(g)

    def reclaim_for_broker(self, k_blocks: int
                           ) -> tuple[int, Optional[ReclaimEvent]]:
        """Victim side of a host steal: the broker (hypervisor) needs
        ``k_blocks`` back.  Recycle idle warm containers (hotmem: the ones
        blocking the free suffix; vanilla: oldest-first until enough blocks
        are free), then unplug — charging this replica's clock with the
        reclaim stall (hotmem: metadata-only; vanilla: migration copies).
        Returns (blocks actually freed, event)."""
        if self.mode == "static":
            return 0, None
        bpp = self.spec.blocks_per_partition
        k_parts = -(-k_blocks // bpp)
        if self.mode == "hotmem":
            self._evict_warm_suffix(k_parts)
            if not self.arena.manager.shrink_plan(k_parts):
                return 0, None
            units = k_parts
        else:
            entries = sorted((t, prof, rid, row)
                             for prof, es in self.warm.items()
                             for (t, rid, row) in es)
            for t, prof, rid, row in entries:
                if self._free_units() >= k_parts:
                    break
                self.arena.finish(rid)
                self.warm[prof].remove((t, rid, row))
            units = k_parts * bpp
            if self.arena.manager.shrink_plan(units)[0] == 0:
                return 0, None        # nothing reclaimable: skip the
                #                       zero-yield migration pass entirely
        ev = self._unplug_now(units, stolen=True)
        return (ev.reclaimed_units *
                (1 if self.mode == "vanilla" else bpp)), ev

    # ----------------------------------------------------------------- run
    def _tick(self, todo: deque) -> None:
        """One scheduler iteration: submit due arrivals, admit, decode (or
        let time pass), recycle idle containers, resize.  ``run`` loops
        this for a standalone replica; ``ClusterSim`` interleaves ticks
        across replicas in virtual-time order.

        Each phase is a host span on the profiler's clock (``engine.grants``,
        ``.orders``, ``.admit``, ``.decode``, ``.decode.post``,
        ``.recycle``, ``.resize``; the start paths, snapshots and resizes
        inside them are spans too).  The self time of each phase lands in
        the ``phases`` of the tick's decode event, if it ran one: ``grants``,
        ``orders``, ``admit`` and ``inputs`` before the step, ``launch``
        and ``wait`` of the step, ``post``, ``recycle`` and ``resize``
        after it."""
        phases: dict[str, float] = {}
        while todo and todo[0].submit_s <= self.now:
            self.submit(todo.popleft())
        # async host pipeline first: claim grant fills (rows grow before
        # admission) and drain one chunk of any open reclaim order — both
        # at this tick boundary, never inside another replica's request.
        # A fully idle engine abandons pending grants: its demand is gone.
        with _Phase(self.events, phases, "grants"):
            self._claim_grants(abandon=not todo and not self.active
                               and not self.pending
                               and not any(self.warm.values()))
        with _Phase(self.events, phases, "orders"):
            self._service_reclaim_orders()
        if not self.active and not self.pending and todo:
            self.now = max(self.now, todo[0].submit_s)
            return
        with _Phase(self.events, phases, "admit") as span:
            scanned, admitted = self._try_admit()
            span.set_metadata(scanned=scanned, admitted=admitted)
        if self._row_req:
            self._decode(phases)
        elif self.pending:
            # stuck in waitqueue: let time pass so warm rows expire /
            # the next resize can plug (regardless of future arrivals)
            self.now += 0.01
        elif not todo and not self.pending and not self.active:
            # drain: idle containers age out, triggering final unplugs
            # (the paper's post-burst scale-down, Fig. 8)
            self.now += self.keep_alive / 8
        with _Phase(self.events, phases, "recycle"):
            self._recycle_idle()
        with _Phase(self.events, phases, "resize"):
            self._resize()

    def run(self, requests: list[Request], max_virtual_s: float = 1e9):
        todo = deque(sorted(requests, key=lambda r: r.submit_s))
        while (todo or self.pending or self.active
               or any(self.warm.values()) or self.host_work()) \
                and self.now < max_virtual_s:
            self._tick(todo)
        return self.metrics()

    def metrics(self) -> dict[str, Any]:
        lat = [r.latency for r in self.done
               if r.latency is not None and r.state is State.DONE]
        reclaims = self.arena.manager.reclaim_events
        return {
            "completed": sum(r.state is State.DONE for r in self.done),
            "killed": sum(r.state is State.KILLED for r in self.done),
            "latency_p50": float(np.percentile(lat, 50)) if lat else None,
            "latency_p99": float(np.percentile(lat, 99)) if lat else None,
            "reclaim_events": len(reclaims),
            "reclaimed_bytes": sum(e.reclaimed_bytes for e in reclaims),
            "migrated_bytes": sum(e.migrated_bytes for e in reclaims),
            "reclaim_wall_s": sum(e.wall_seconds for e in reclaims),
            "decode_steps": sum(1 for e in self.events
                                if e.kind == "decode"),
            "cold_starts": self.cold_starts,
            "warm_starts": self.warm_starts,
            "restore_starts": self.restore_starts,
            "remote_restore_starts": self.remote_restore_starts,
            "snapshots_taken": sum(1 for e in self.events
                                   if e.kind == "snapshot"),
            "events": self.events,
        }
