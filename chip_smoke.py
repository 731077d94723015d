#!/usr/bin/env python3
"""Serve qwen2-1.5b at its published widths on one TPU chip, and check it.

Two HotMem ``ServeEngine`` replicas (A and B) share one
``HostMemoryBroker`` (async reclaim orders, a host snapshot pool) on the
one chip, under ``ClusterSim`` with a pinned router.  Weights are random
from ``--seed``; each replica's arena holds 64 partitions of 2048 tokens
(56 MiB of KV per partition, 3.5 GiB at its largest bucket).  The traffic
walks every start path and the reclaim plane:

  B: three cold prefills, then a warm start of a kept-alive container;
  A: a 20-request burst that outgrows the host budget, so the broker
     orders B to unplug partitions (B drains the order: reclaimed bytes);
  B's surviving warm containers expire and are captured into the pool
     (fused Pallas capture, one device->host copy each);
  A: a late tail that restores those snapshots (fused Pallas restore).

Then it checks the results by the repo's own means: every request
completed; the start-path, kernel-launch and transfer counters; a pooled
row restored and captured again is byte-identical, and the Pallas capture
equals the jnp reference on that arena; decode logits are finite and a
prefill + cached decode agrees with the full forward pass.

Earlier lines print per-phase walls and device facts as bring-up
observations (not metrics).  The last line is exactly
``{"ok": true, "device": {...}}``.  Off a TPU it exits non-zero at once.

  python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2-1.5b"
N_PARTITIONS = 64          # per replica; 56 MiB each at 2048 tokens
# Virtual seconds, spaced so that compile walls inside timed regions (the
# first prefill of each prompt length) cannot reorder the phases.  B keeps
# containers alive long enough to be ordered and later captured.  A's
# burst containers outlive the lead B's idle clock can take (a drain tick
# jumps keep_alive / 8 = 37.5 s), so B drains the order before any release
# of A's could cover it; they still expire long before B's, so A has
# shrunk when B's captures need budget room.
KEEP_ALIVE_A, KEEP_ALIVE_B = 60.0, 300.0


def _require(ok, why) -> None:
    """A phase check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(why)


def _requests():
    from repro.serving.request import PROFILES, Request
    reqs = [Request(rid=f"b{i}", profile=PROFILES[p], submit_s=0.0)
            for i, p in enumerate(("cnn", "bert", "bfs"))]
    reqs.append(Request(rid="b3", profile=PROFILES["cnn"], submit_s=200.0))
    names = sorted(PROFILES)
    reqs += [Request(rid=f"a{i}", profile=PROFILES[names[i % 4]],
                     submit_s=210.0) for i in range(20)]
    reqs += [Request(rid=f"t{i}", profile=PROFILES[p], submit_s=1000.0)
             for i, p in enumerate(names)]
    return reqs


def _rel_err(cfg, params, partition_tokens: int, seed: int) -> float:
    """Prefill of S tokens + one cached decode step vs the full forward
    pass over S+1 tokens (the repo's decode-consistency oracle)."""
    from repro.models import model as M
    s = 16
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, s + 1), 0,
                              cfg.vocab_size)
    full = jax.jit(lambda p, t: M.forward_train(
        cfg, p, {"tokens": t}, remat=False))(params, toks)[:, s]
    caches = M.init_caches(cfg, 1, partition_tokens)
    _, caches = jax.jit(lambda p, t, c: M.prefill(
        cfg, p, {"tokens": t}, c))(params, toks[:, :s], caches)
    lg, _ = jax.jit(lambda p, t, po, c: M.decode_step(cfg, p, t, po, c))(
        params, toks[:, s:], jnp.full((1,), s, jnp.int32), caches)
    full, lg = full.astype(jnp.float32), lg.astype(jnp.float32)
    _require(bool(jnp.isfinite(lg).all()), "decode logits are not finite")
    return float(jnp.max(jnp.abs(full - lg)) / (jnp.max(jnp.abs(full))
                                                  + 1e-9))


def _max_rows(eng) -> int:
    """Largest arena (rows) the replica held, replayed from its plug and
    unplug events."""
    rows = peak = eng.ladder[0]
    per_row = eng.spec.bytes_per_partition
    for ev in eng.events:
        if ev.kind == "plug":
            rows += ev.detail["units"]
        elif ev.kind == "unplug":
            rows -= ev.detail["reclaimed_bytes"] // per_row
        peak = max(peak, rows)
    _require(rows == eng._rows(), (rows, eng._rows()))
    return peak


def run_smoke(cfg, *, partition_tokens: int = 2048, seed: int = 0) -> dict:
    """Serve the smoke traffic on ``cfg`` and assert every phase.
    Returns the observations; raises AssertionError on a failed phase."""
    from repro.cluster import ClusterSim, HostMemoryBroker, Router
    from repro.core.arena import ArenaSpec
    from repro.kernels import kv_snapshot
    from repro.models import model as M
    from repro.serving.engine import ServeEngine

    obs: dict = {}
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    obs["init_params_s"] = time.perf_counter() - t0

    spec = ArenaSpec.from_model(cfg, partition_tokens=partition_tokens,
                                n_partitions=N_PARTITIONS,
                                block_tokens=partition_tokens // 16)
    bpp = spec.blocks_per_partition
    # A's burst asks for 30 more partitions while only 28 are free: the
    # broker orders B to give back the rest.  The pool holds 4 rows.
    broker = HostMemoryBroker(budget_units=34 * bpp, async_reclaim=True,
                              snapshot_pool_units=4 * bpp)
    t0 = time.perf_counter()
    engines = {rid: ServeEngine(cfg, params, spec, mode="hotmem",
                                keep_alive=ka, seed=i, broker=broker,
                                replica_id=rid)
               for i, (rid, ka) in enumerate((("A", KEEP_ALIVE_A),
                                              ("B", KEEP_ALIVE_B)))}
    obs["build_engines_s"] = time.perf_counter() - t0
    obs["snapshot_impl"] = sorted({e.snapshot_impl
                                   for e in engines.values()})

    kv_snapshot.reset_stats()
    reqs = _requests()
    sim = ClusterSim(engines, Router(route_fn=lambda r, e:
                                     "B" if r.rid.startswith("b") else "A"),
                     broker)
    t0 = time.perf_counter()
    m = sim.run(reqs, max_virtual_s=1e4)
    obs["serve_s"] = time.perf_counter() - t0
    broker.check_invariants()
    stats = dict(kv_snapshot.STATS)
    obs.update(stats)
    for k in ("completed", "killed", "cold_starts", "warm_hits",
              "restore_starts", "reclaimed_bytes", "truncated"):
        obs[k] = m[k]
    obs["max_rows"] = {rid: _max_rows(e) for rid, e in engines.items()}
    obs["row_bytes"] = engines["A"]._snapshot_layout().row_bytes
    drained = [s for s in broker.steal_log
               if s.victim == "B" and not s.natural]
    obs["order_drains"] = len(drained)
    # device blobs the paged restore index keeps alive (never freed)
    obs["device_page_bytes"] = sum(
        {id(dev): int(dev.nbytes) for e in engines.values()
         for dev, _s, _e in e._device_pages.values()}.values())
    print("serve: " + json.dumps({k: obs[k] for k in (
        "completed", "cold_starts", "warm_hits", "restore_starts",
        "order_drains", "reclaimed_bytes", "max_rows",
        "capture_launches", "restore_launches", "d2h_transfers",
        "h2d_transfers")}))

    _require(not m["truncated"], "the run was cut before it finished")
    _require(m["completed"] == len(reqs) and m["killed"] == 0,
             f"{m['completed']} of {len(reqs)} requests completed, "
             f"{m['killed']} killed")
    _require(m["cold_starts"] >= 1, "no cold prefill ran")
    _require(m["warm_hits"] >= 1, "no warm start ran")
    _require(m["restore_starts"] >= 1, "no restore from the pool ran")
    _require(drained, "B never drained a reclaim order")
    _require(all(s.reclaimed_bytes > 0 for s in drained), drained)
    _require(m["reclaimed_bytes"] > 0, "no unplug reclaimed bytes")
    _require(stats["capture_launches"] >= 1, "no capture launched")
    _require(stats["restore_launches"] >= 1, "no restore launched")
    # one device->host copy of exactly one row per capture
    _require(stats["d2h_transfers"] == stats["capture_launches"] and
             stats["d2h_bytes"] == stats["d2h_transfers"] * obs["row_bytes"],
             stats)
    _require(1 <= stats["h2d_transfers"] <= stats["restore_launches"], stats)

    # a pooled row, restored into the live arena and captured again,
    # comes back byte-identical — and Pallas capture equals the reference
    t0 = time.perf_counter()
    eng = engines["A"]
    layout = eng._snapshot_layout()
    pool = broker.snapshots
    key = next(k for k in pool.keys() if broker.snapshot_restorable(k))
    blob_u8 = pool.peek(key).payload.blob
    host = blob_u8.view(np.dtype(layout.dtype)).reshape(1, -1)
    row = jnp.asarray([eng._rows() - 1], jnp.int32)
    restored = M.cache_write_rows(eng.caches, jnp.asarray(host), row,
                                  layout=layout, impl="pallas")
    both = jnp.asarray([eng._rows() - 1, 0], jnp.int32)
    again = np.asarray(jax.device_get(M.cache_read_rows(
        restored, both, layout=layout, impl="pallas")))
    want = np.asarray(jax.device_get(M.cache_read_rows(
        restored, both, layout=layout, impl="ref")))
    _require(again[0].tobytes() == blob_u8.tobytes(),
             "restore -> capture round trip changed the row's bytes")
    _require(again.tobytes() == want.tobytes(),
             "Pallas capture differs from the jnp reference")

    # decode logits on that arena: finite, of the expected shape
    rows = eng._rows()
    logits, _ = eng._decode_jit[rows](
        params, jnp.zeros((rows, 1), jnp.int32),
        jnp.full((rows,), 1, jnp.int32), restored)
    _require(logits.shape[0] == rows, logits.shape)
    _require(bool(jnp.isfinite(logits.astype(jnp.float32)).all()),
             "decode logits are not finite")
    obs["decode_rel_err"] = _rel_err(cfg, params, partition_tokens, seed)
    _require(obs["decode_rel_err"] < 0.05,
             f"cached decode disagrees with the full forward: "
             f"{obs['decode_rel_err']}")
    obs["checks_s"] = time.perf_counter() - t0
    return obs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's backend is {backend!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    from repro.configs.base import get_config
    from repro.kernels import ops

    cache_dir = enable_compile_cache()
    _require(ops._on_tpu(), "kernels would run interpreted")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    obs = run_smoke(get_config(ARCH), seed=args.seed)
    _require(obs["snapshot_impl"] == ["pallas"], obs["snapshot_impl"])
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print("bring-up observations (walls, not metrics): " + json.dumps(
        {k: obs[k] for k in ("init_params_s", "build_engines_s", "serve_s",
                             "checks_s")} | {"total_s":
                                             time.perf_counter() - t0}))
    print(f"peak_bytes_in_use: {peak}; row_bytes: {obs['row_bytes']}; "
          f"device_page_bytes: {obs['device_page_bytes']}; "
          f"decode_rel_err: {obs['decode_rel_err']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
