"""Multi-replica host demo: one memory budget per host, VM replicas, a
broker per host — and, with ``--hosts N``, a fleet of hosts.

Replica B handles early steady load then idles (kept-alive containers);
replica A's later burst outgrows the host's free pool, so the broker
reclaims B's memory — sub-second and zero-copy under HotMem, migration
copies under the vanilla paged baseline.

Each mode runs twice: with the synchronous broker (A's plug request
serializes behind B's unplug — the ``stall_p99`` column is what A waits)
and with the async reclaim pipeline (B receives a ``ReclaimOrder`` and
drains it between its own ticks while A keeps decoding; A's stall is 0
and the grant completes incrementally).

``--policy`` selects the router: the default ``pinned`` route reproduces
the classic steal scenario; any ``repro.cluster.router`` policy name
spreads the shared trace instead.  ``snapshot_affinity`` and
``drain_weighted`` also enable the host snapshot pool: expiring warm
containers are copied out and later invocations restore from the pool
instead of prefilling (the ``warm``/``restore`` columns count
engine-side start paths; ``squeezed`` counts snapshot units the broker
dropped — metadata-only — to cover grants).

``--hosts N`` splits the replicas across N hosts (one broker + budget +
snapshot pool each, placed via ``FleetScheduler`` spread placement) and
runs them under ``FleetSim``.  Budgets are then per-host uncontended, so
steals vanish — what appears instead is cross-host warm-state migration:
B's expired containers are captured on B's host, and the late tail
pinned to A pulls those snapshots over (``mig`` column; modeled
inter-host copy over real payload bytes), so A restores remotely
(``remote`` column) instead of cold-prefilling.

``--devices N`` gives every host an N-device mesh: each replica's KV
stripes one shard per device, the broker arbitrates per-device budgets
(reclaim orders drain one unit per shard in lockstep), and the table
grows a per-device occupancy line per host (free/granted/snapshot units
on every device — balanced throughout, which is the point).  Vanilla
mode plugs single blocks, which cannot stripe, so ``--devices > 1``
requires ``--modes hotmem``.

``--scenario NAME`` runs one entry of the multi-tenant scenario bank
(``repro.cluster.scenarios``) instead of the engine demo and prints its
report row — the same deterministic rows ``benchmarks/run.py
--scenarios`` gates against ``BENCH_6.json``/``BENCH_7.json``/
``BENCH_8.json``.

``--autoscale`` runs the host-lifecycle scenarios (the ``autoscale``
family): a burst boots hosts through the low-water slack mark, the
quiet tail retires the emptiest host, and retirement DRAINS the host's
snapshot pool to peers over the contended interconnect instead of
discarding it.  Prints a per-scenario lifecycle summary (boots,
retires, migrations, TTFT).

``--dedup`` demos the content-addressed snapshot store on real engines:
several functions with byte-identical prompts are captured as page
manifests (``--page-size`` bytes per page, also honored by the main
demo), so the pool charges each unique page ONCE (unique vs referenced
units) and a second replica's restores find the shared pages already
mapped — copy-on-write, reported as the shared-page restore ratio.

  PYTHONPATH=src python examples/cluster_demo.py
  PYTHONPATH=src python examples/cluster_demo.py \
      --policy snapshot_affinity --modes hotmem
  PYTHONPATH=src python examples/cluster_demo.py --hosts 2 --modes hotmem
  PYTHONPATH=src python examples/cluster_demo.py --devices 2 --modes hotmem
  PYTHONPATH=src python examples/cluster_demo.py --scenario slo_tiered
  PYTHONPATH=src python examples/cluster_demo.py --autoscale
  PYTHONPATH=src python examples/cluster_demo.py --dedup --page-size 4096
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.cluster import (ClusterSim, DeviceTopology, FleetScheduler,
                           FleetSim, HostMemoryBroker, Router)
from repro.cluster.router import POLICIES
from repro.configs.base import get_config, reduced
from repro.core.arena import ArenaSpec
from repro.models import model as M
from repro.serving.engine import ServeEngine
from repro.serving.request import PROFILES, Request
from repro.serving.tracegen import assign_profiles, bursty_trace


def _reqs(pooled: bool):
    quiet = bursty_trace(6.0, 0.9, burst_x=1.0, burst_len=0.0, seed=2)
    burst = [4.0 + t for t in bursty_trace(4.0, 3.0, burst_x=3.0,
                                           burst_at=(0.0,), burst_len=2.0,
                                           seed=3)]
    reqs = [Request(rid=f"b{i}", profile=p, submit_s=t)
            for i, (t, p) in enumerate(assign_profiles(quiet, PROFILES, 2))]
    reqs += [Request(rid=f"a{i}", profile=p, submit_s=t)
             for i, (t, p) in enumerate(assign_profiles(burst, PROFILES, 3))]
    if pooled:
        # a late tail, arriving after every warm container has expired
        # (and been captured): these invocations restore from the pool —
        # cross-host under --hosts > 1 — instead of prefilling
        reqs += [Request(rid=f"t{i}", profile=PROFILES[p],
                         submit_s=12.0 + 0.5 * i)
                 for i, p in enumerate(("cnn", "bert", "bfs", "html"))]
    return reqs


def _dedup_demo(args) -> None:
    """Content-addressed pool on real engines: N functions whose cold
    prompts are byte-identical produce byte-identical prefix KV, so
    their page manifests share every digest — the pool charges ONE copy
    (unique vs referenced units) and a second replica's restores find
    the shared pages already mapped (copy-on-write, no re-copy)."""
    import dataclasses

    cfg = reduced(get_config("qwen2-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = ArenaSpec.from_model(cfg, partition_tokens=128, n_partitions=8,
                                block_tokens=32)
    bpp = spec.blocks_per_partition
    page_bytes = args.page_size or 4096
    broker = HostMemoryBroker(budget_units=12 * bpp,
                              snapshot_pool_units=6 * bpp)
    cap = ServeEngine(cfg, params, spec, keep_alive=0.4, seed=0,
                      broker=broker, replica_id="A",
                      snapshot_page_bytes=page_bytes)
    rst = ServeEngine(cfg, params, spec, keep_alive=0.4, seed=1,
                      broker=broker, replica_id="B",
                      snapshot_page_bytes=page_bytes)
    # the engine's cold prompt is np.full(prompt_tokens, hash(name) % 97
    # + 1): same residue + same token count = byte-identical prompt =
    # byte-identical prefix KV.  hash() is salted per process, so SEARCH
    # for colliding names instead of hardcoding them.
    base = PROFILES["cnn"]
    names, i = ["dup0"], 1
    while len(names) < 4 and i < 100_000:
        if hash(f"dup{i}") % 97 == hash("dup0") % 97:
            names.append(f"dup{i}")
        i += 1
    assert len(names) == 4
    profs = {n: dataclasses.replace(base, name=n) for n in names}

    # phase 1: replica A runs every function cold; run() drains until the
    # warm containers age out, capturing each as a page manifest
    cap.run([Request(rid=f"c{j}", profile=profs[n], submit_s=0.2 * j)
             for j, n in enumerate(names)], max_virtual_s=200)
    assert all(broker.snapshot_restorable(n) for n in names), \
        "captures did not land in the pool"
    broker.check_invariants()
    pool = broker.snapshots
    ref, uniq = pool.referenced_units, broker.snapshot_units()

    # phase 2: replica B (never ran any of them) restores all four; after
    # the first manifest materializes, the rest map already-shared pages
    rst.run([Request(rid=f"r{j}", profile=profs[n], submit_s=0.0)
             for j, n in enumerate(names)], max_virtual_s=200)
    broker.check_invariants()
    restores = [e for e in rst.events if e.kind == "restore"]
    total = sum(e.detail["pages_total"] for e in restores)
    shared = sum(e.detail["pages_shared"] for e in restores)

    print(f"page_size={page_bytes}B  functions={len(names)} "
          f"(byte-identical {base.prompt_tokens}-token prompts)")
    print(f"{'referenced_units':>16s} {'unique_units':>12s} "
          f"{'dedup_ratio':>11s} {'restores':>8s} {'pages':>6s} "
          f"{'shared':>6s} {'cow_ratio':>9s}")
    print(f"{ref:16d} {uniq:12d} "
          f"{(uniq / ref if ref else 1.0):11.3f} "
          f"{len(restores):8d} {total:6d} {shared:6d} "
          f"{(shared / total if total else 0.0):9.3f}")
    print("\nEvery function's prefix KV is byte-identical, so the"
          "\ncontent-addressed pool stores and charges each page once:"
          "\nunique_units is what the ledger's snapshot account holds,"
          "\nreferenced_units what the manifests add up to.  Replica B"
          "\nnever ran these functions; its first restore materializes"
          "\nthe pages, and the remaining restores find them already"
          "\nmapped (shared/pages) — they remap copy-on-write instead"
          "\nof paying the copy wall again (cow_ratio).")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="pinned",
                    choices=("pinned",) + POLICIES,
                    help="router policy (pinned = quiet load on B, "
                         "burst on A — the classic steal scenario)")
    ap.add_argument("--modes", default="hotmem,vanilla",
                    help="comma-separated engine modes to run")
    ap.add_argument("--hosts", type=int, default=1,
                    help="number of hosts; > 1 places replicas across "
                         "per-host brokers and enables cross-host "
                         "snapshot migration (FleetSim)")
    ap.add_argument("--devices", type=int, default=1,
                    help="devices per host: > 1 stripes every replica's "
                         "KV one shard per device behind per-device "
                         "broker budgets and prints per-device occupancy "
                         "(hotmem only — vanilla cannot stripe)")
    ap.add_argument("--scenario", default=None,
                    help="run one scenario-bank entry (see "
                         "repro.cluster.scenarios.SCENARIOS) and print "
                         "its report row instead of the engine demo")
    ap.add_argument("--autoscale", action="store_true",
                    help="run the host-lifecycle (autoscale family) "
                         "scenarios and print a lifecycle summary "
                         "instead of the engine demo")
    ap.add_argument("--dedup", action="store_true",
                    help="demo the content-addressed snapshot store: "
                         "capture functions with identical prompts as "
                         "page manifests and print unique vs referenced "
                         "units plus the shared-page restore ratio")
    ap.add_argument("--page-size", type=int, default=None,
                    help="content-addressed snapshot page size in bytes "
                         "(enables paged capture on the demo engines; "
                         "--dedup defaults to 4096)")
    ap.add_argument("--seed", type=int, default=0,
                    help="scenario seed (--scenario/--autoscale only)")
    args = ap.parse_args()
    assert args.hosts >= 1
    assert args.devices >= 1
    assert args.page_size is None or args.page_size > 0
    assert args.devices == 1 or "vanilla" not in args.modes.split(","), \
        "--devices > 1 requires --modes without vanilla (single-block " \
        "plugs cannot stripe over a mesh)"

    if args.dedup:
        _dedup_demo(args)
        return

    if args.autoscale:
        from repro.cluster.scenarios import SCENARIOS, run_scenario
        names = sorted(n for n, (fam, _) in SCENARIOS.items()
                       if fam == "autoscale")
        print(f"{'scenario':16s} {'reqs':>5s} {'hosts':>5s} {'boots':>5s} "
              f"{'retires':>7s} {'mig':>4s} {'warm':>5s} {'restore':>7s} "
              f"{'cold':>5s} {'host_s':>8s} {'p99_ms':>8s}")
        for name in names:
            row = run_scenario(name, seed=args.seed)
            p99 = max(v for v in row["ttft_p99_ms_by_tier"].values())
            print(f"{name:16s} {row['requests']:5d} {row['hosts']:5d} "
                  f"{row['host_boots']:5d} {row['host_retires']:7d} "
                  f"{row['snapshot_migrations']:4d} "
                  f"{row['warm_starts']:5d} {row['restore_starts']:7d} "
                  f"{row['cold_starts']:5d} {row['host_seconds']:8.3f} "
                  f"{p99:8.2f}")
        print("\nBursts eat the fleet's free-unit slack through the"
              "\nlow-water mark, so the autoscaler boots hosts; the quiet"
              "\ntail holds slack at the high-water mark until the"
              "\nemptiest host retires.  A retiring host stops taking"
              "\nroutes, drains its snapshot pool to peers over the"
              "\ncontended interconnect (concurrent transfers sharing an"
              "\nendpoint split its bandwidth), and is removed only once"
              "\nits ledger shows every unit back home — warm state"
              "\nsurvives scale-down instead of being discarded.")
        return

    if args.scenario is not None:
        import json

        from repro.cluster.scenarios import SCENARIOS, run_scenario
        assert args.scenario in SCENARIOS, \
            f"unknown scenario {args.scenario!r} " \
            f"(have {', '.join(sorted(SCENARIOS))})"
        row = run_scenario(args.scenario, seed=args.seed)
        print(json.dumps(row, indent=1))
        return

    cfg = reduced(get_config("qwen2-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = ArenaSpec.from_model(cfg, partition_tokens=128, n_partitions=8,
                                block_tokens=32)
    bpp = spec.blocks_per_partition
    # the snapshot pool is paid for by the policies that exploit it —
    # and always on a fleet, where it is what migration moves
    pooled = args.policy in ("snapshot_affinity", "drain_weighted",
                             "slo_tiered") or args.hosts > 1
    pool_units = 4 * bpp if pooled else None
    # one replica per host (min 2, so the steal/pinned scenario exists)
    rids = [chr(ord("A") + k) for k in range(max(2, args.hosts))]

    print(f"policy={args.policy} hosts={args.hosts}")
    print(f"{'mode':10s} {'broker':6s} {'completed':>9s} {'steals':>6s} "
          f"{'stall_p99_ms':>12s} {'steal_ms':>9s} {'migratedKiB':>11s} "
          f"{'lat_p99_s':>9s} {'warm':>5s} {'restore':>7s} {'remote':>6s} "
          f"{'mig':>4s} {'squeezed':>8s}")
    for mode in args.modes.split(","):
        for async_mode in (False, True):
            # single host: 10 partitions' worth — less than 2 full arenas,
            # so A's burst cannot grow without shrinking B (or squeezing
            # the snapshot pool first, when one exists).  Fleet: each
            # host holds a full arena's budget (uncontended — the
            # cross-host traffic is snapshots, not steals).
            budget = (10 if args.hosts == 1 else 12) * bpp
            topo = DeviceTopology.uniform(budget, args.devices) \
                if args.devices > 1 else None
            sched = FleetScheduler()
            for k in range(args.hosts):
                sched.add_host(f"h{k}", HostMemoryBroker(
                    budget_units=budget, async_reclaim=async_mode,
                    snapshot_pool_units=pool_units, topology=topo))
            start_units = min(2, spec.n_partitions) * bpp
            hosts_map = {h: {} for h in sched.brokers}
            for i, rid in enumerate(rids):
                host = sched.place(rid, start_units, policy="spread")
                hosts_map[host][rid] = ServeEngine(
                    cfg, params, spec, mode=mode, keep_alive=3.0, seed=i,
                    broker=sched.brokers[host], replica_id=rid,
                    snapshot_page_bytes=args.page_size)
            if args.policy == "pinned":
                router = Router(route_fn=lambda r, e:
                                "B" if r.rid.startswith("b") else "A")
            else:
                router = Router(args.policy)
            if args.hosts == 1:
                sim = ClusterSim(hosts_map["h0"], router,
                                 sched.brokers["h0"])
            else:
                sim = FleetSim(hosts_map, router, scheduler=sched)
            m = sim.run(_reqs(pooled), max_virtual_s=2000)
            sched.check_invariants()
            reps = [b.report() for b in sched.brokers.values()]
            by_mode = [r["by_mode"].get(mode, {}) for r in reps]
            stalls = sum((b.request_stalls for b in
                          sched.brokers.values()), []) or [0.0]
            print(f"{mode:10s} {'async' if async_mode else 'sync':6s} "
                  f"{m['completed']:9d} "
                  f"{sum(r['steals'] for r in reps):6d} "
                  f"{float(np.percentile(stalls, 99)) * 1e3:12.2f} "
                  f"{sum(d.get('wall_seconds', 0.0) for d in by_mode) * 1e3:9.2f} "
                  f"{sum(d.get('migrated_bytes', 0) for d in by_mode) / 1024:11.1f} "
                  f"{(m['latency_p99'] or 0):9.2f} "
                  f"{m['warm_hits']:5d} {m['restore_starts']:7d} "
                  f"{m['remote_restore_starts']:6d} "
                  f"{m['snapshot_migrations']:4d} "
                  f"{sum(r['squeezed_units'] for r in reps):8d}")
            if args.devices > 1:
                # per-device occupancy: free/granted/snapshot units on
                # every device of each host's mesh at end of run
                for h, b in sorted(sched.brokers.items()):
                    cols = b.ledger.device_report()
                    occ = "  ".join(
                        f"d{d}[free={c['free']} granted={c['granted']} "
                        f"snap={c['snapshot']}]"
                        for d, c in enumerate(cols))
                    print(f"{'':17s} {h}: {occ}")
    print("\nThe broker reclaims the idle replica's memory for the loaded"
          "\none; HotMem makes that host-level steal zero-copy, the paged"
          "\nbaseline pays real migration bytes for the same elasticity —"
          "\nand the async reclaim pipeline removes the requester-visible"
          "\nstall entirely (stall_p99 -> 0): victims drain ReclaimOrders"
          "\nbetween their own ticks while the requester keeps decoding."
          "\nWith --policy snapshot_affinity or drain_weighted the host"
          "\nalso pools expired warm containers' prefix KV: later"
          "\ninvocations restore from the pool instead of prefilling, and"
          "\nunder pressure the broker squeezes those snapshot units"
          "\nfirst (metadata-only) before ordering any VM to shrink."
          "\nWith --hosts N the fleet scheduler places replicas across"
          "\nper-host budgets and migrates snapshots between hosts (mig)"
          "\nso a host that never ran a function restores its warm state"
          "\nremotely (remote) — paying the modeled inter-host copy,"
          "\nstill far below a cold prefill.")


if __name__ == "__main__":
    main()
