"""Fleet layer: multi-host placement, cross-host snapshot migration, the
drain-weighted router, and the FleetSim/ClusterSim seam.

Fast tests drive schedulers, brokers, and stub replicas as pure metadata
— per-host conservation (every host's ``free + granted + escrow +
snapshot == budget``) is asserted after EVERY fleet event via
``FleetScheduler.check_invariants``.  The properties pinned down:

  (a) placement: ``spread``/``pack`` are deterministic capacity policies
      (droppable snapshot charge counts as capacity);
  (b) migration: moving a snapshot between hosts debits the source
      ledger, credits the destination ledger, charges the modeled
      inter-host copy (real bytes / configurable bandwidth + link
      latency) onto the entry, and is refused — nothing mutated — when
      no peer holds a restorable copy or the destination lacks room;
  (c) ``drain_weighted`` routing: start-path tiers (local warm > local
      snapshot > remote snapshot > cold) and WEIGHTED drain scoring
      (how many blocks a replica owes, not whether it owes), plus the
      ``drain_avoided`` accounting shared with ``snapshot_affinity``;
  (d) the seam: ``FleetSim`` with one host replays ``ClusterSim``
      exactly (stub schedules here; the bit-identical real-engine
      ``StepEvent`` regression is the slow test below).

The ``slow``-marked tests run real ``ServeEngine`` replicas: the
single-host StepEvent trace regression and the remote-restore E2E
(capture on host B, fleet migration, restore on host A tagged
``source="remote"`` with the copy charge, TTFT between local restore
and cold prefill).
"""
from collections import deque

import pytest

from repro.cluster import (ClusterSim, FleetScheduler, FleetSim,
                           HostMemoryBroker, Router)
from repro.cluster.snapshots import Snapshot
from repro.serving.request import PROFILES, Request

from conftest import StubReplica, fake_clock as _fake_clock, \
    mk_async_broker as _mk_async


def _mk_fleet(budgets, *, pool_units=None, bandwidth=1024.0, latency=0.5):
    """Fleet of sync brokers on a fake clock; ``budgets`` maps host ->
    budget units.  Bandwidth in bytes/virtual-second so modeled copy
    walls are exact small numbers."""
    sched = FleetScheduler(bandwidth_bytes_per_s=bandwidth,
                           link_latency_s=latency, clock=_fake_clock())
    for h, b in budgets.items():
        sched.add_host(h, HostMemoryBroker(
            b, clock=_fake_clock(), snapshot_pool_units=pool_units))
    return sched


# ---------------------------------------------------------- (a) placement


def test_place_spread_and_pack_deterministic():
    sched = _mk_fleet({"h0": 16, "h1": 16, "h2": 16})
    sched.brokers["h0"].register("x", 10)      # capacities: 6, 16, 16
    sched.check_invariants()
    assert sched.place("a", 4, policy="spread") == "h1"   # most free, tie->id
    sched.brokers["h1"].register("a", 4)       # boot: capacities 6, 12, 16
    assert sched.place("b", 4, policy="pack") == "h0"     # best fit
    sched.brokers["h0"].register("b", 4)       # capacities 2, 12, 16
    assert sched.place("c", 8, policy="pack") == "h1"     # h0 can't fit 8
    sched.check_invariants()
    assert sched.placements == {"a": "h1", "b": "h0", "c": "h1"}
    assert sched.host_of("a") == "h1" and sched.host_of("zz") is None
    assert sched.broker_of("b") is sched.brokers["h0"]
    assert sched.broker_of("zz") is None
    assert sched.report()["placements"]["a"] == "h1"
    with pytest.raises(AssertionError):
        sched.place("d", 99)                   # fits nowhere: loud
    with pytest.raises(AssertionError):
        sched.place("a", 1)                    # already placed


def test_capacity_counts_droppable_snapshot_charge():
    """A booting replica squeezes the destination pool, so snapshot units
    are reclaimable capacity for placement purposes."""
    sched = _mk_fleet({"h0": 8, "h1": 8}, pool_units=8)
    sched.brokers["h0"].register("x", 2)                  # free 6
    assert sched.brokers["h1"].snapshot_put("cnn", units=7)   # free 1
    sched.check_invariants()
    assert sched.capacity("h0") == 6
    assert sched.capacity("h1") == 8           # 1 free + 7 droppable
    assert sched.place("a", 7, policy="spread") == "h1"


# --------------------------------------------------------- (b) migration


def test_migration_scripted_per_host_conservation():
    """THE fleet acceptance property: a cross-host migration debits the
    source pool, credits the destination pool, charges the modeled copy
    — and every host's ledger conserves after every event."""
    sched = _mk_fleet({"h0": 16, "h1": 16}, pool_units=8,
                      bandwidth=1024.0, latency=0.5)
    src, dst = sched.brokers["h1"], sched.brokers["h0"]
    src.register("B", 4)
    dst.register("A", 4)
    sched.check_invariants()
    assert src.snapshot_put("cnn", units=3, nbytes=2048,
                            payload=object(), replica_id="B")
    sched.check_invariants()
    assert src.free_units == 9 and src.snapshot_units() == 3
    assert dst.snapshot_units() == 0

    rec = sched.ensure_local("cnn", "h0")
    sched.check_invariants()
    assert rec is not None
    assert (rec.key, rec.src, rec.dst) == ("cnn", "h1", "h0")
    assert rec.units == 3 and rec.nbytes == 2048
    # modeled copy: latency + bytes/bandwidth, on the fleet clock
    assert rec.copy_seconds == pytest.approx(0.5 + 2048 / 1024.0)
    # debit/credit landed on the right ledgers
    assert src.snapshot_units() == 0 and src.free_units == 12
    assert dst.snapshot_units() == 3 and dst.free_units == 9
    assert not src.snapshot_available("cnn")
    assert dst.snapshot_restorable("cnn")
    snap = dst.snapshots.peek("cnn")
    assert snap.origin_host == "h1"
    assert snap.copy_seconds == rec.copy_seconds
    assert sched.report()["migrations"] == 1
    assert sched.report()["migrated_snapshot_bytes"] == 2048

    # already local: ensure_local is a no-op, nothing new moves
    assert sched.ensure_local("cnn", "h0") is None
    sched.check_invariants()
    assert len(sched.migrations) == 1

    # the copy charge is paid exactly once
    assert snap.claim_copy() == rec.copy_seconds
    assert snap.claim_copy() == 0.0


def test_migration_refused_without_source_or_room():
    sched = _mk_fleet({"h0": 8, "h1": 8}, pool_units=4)
    sched.brokers["h0"].register("A", 2)
    sched.brokers["h1"].register("B", 2)
    # no peer holds the key at all
    assert sched.ensure_local("cnn", "h0") is None
    assert sched.migration_denied == 1
    # a metadata-only entry (no payload) can never serve a restore, so it
    # is not a migration source either
    assert sched.brokers["h1"].snapshot_put("cnn", units=2)
    sched.check_invariants()
    assert sched.ensure_local("cnn", "h0") is None
    assert sched.migration_denied == 2
    # destination without room: source keeps the snapshot, nothing moves
    assert sched.brokers["h1"].snapshot_put("bert", units=2,
                                            payload=object())
    sched.brokers["h0"].request_units("A", 6)             # drain h0 free
    sched.check_invariants()
    assert sched.brokers["h0"].free_units == 0
    assert sched.ensure_local("bert", "h0") is None
    sched.check_invariants()
    assert sched.migration_denied == 3
    assert sched.brokers["h1"].snapshot_restorable("bert")
    assert not sched.brokers["h0"].snapshot_available("bert")
    assert not sched.migrations


def test_migration_compounds_unpaid_copy_walls():
    """A snapshot migrated twice without a restore in between owes BOTH
    hops at its first restore (the transfer wall never silently drops) —
    and the second hop CONTENDS with the first: hop1 (h0->h1, started at
    clock 1.0, in flight until 1.0 + 1.25) still occupies h1's NIC when
    hop2 (h1->h2) starts at clock 2.0, so hop2's byte wall sees half the
    pipe."""
    sched = _mk_fleet({"h0": 8, "h1": 8, "h2": 8}, pool_units=4,
                      bandwidth=1024.0, latency=0.25)
    for h in ("h0", "h1", "h2"):
        sched.brokers[h].register(f"r{h}", 2)
    assert sched.brokers["h0"].snapshot_put("cnn", units=2, nbytes=1024,
                                            payload=object())
    hop1 = sched.migrate_snapshot("cnn", "h1")
    sched.check_invariants()
    hop2 = sched.migrate_snapshot("cnn", "h2")
    sched.check_invariants()
    assert hop1.copy_seconds == pytest.approx(0.25 + 1.0)
    assert hop2.copy_seconds == pytest.approx((0.25 + 1.0)      # hop1 owed
                                              + 0.25 + 2 * 1.0)
    snap = sched.brokers["h2"].snapshots.peek("cnn")
    assert snap.origin_host == "h1"
    assert snap.claim_copy() == pytest.approx(hop2.copy_seconds)


def test_snapshot_host_is_deterministic_and_excludes_dst():
    sched = _mk_fleet({"h0": 8, "h1": 8, "h2": 8}, pool_units=4)
    for h in ("h1", "h2"):
        assert sched.brokers[h].snapshot_put("cnn", units=1,
                                             payload=object())
    assert sched.snapshot_host("cnn") == "h1"             # lowest host id
    assert sched.snapshot_host("cnn", exclude="h1") == "h2"
    assert sched.snapshot_host("nope") is None


# --------------------------------------------- (c) drain-weighted routing


class _FakeEngine:
    def __init__(self, load, warm=()):
        self._load = load
        self.warm = {name: [(0.0, "rid", 0)] for name in warm}

    def load(self):
        return self._load


def _req(profile="cnn"):
    return Request(rid="x", profile=PROFILES[profile], submit_s=0.0)


def test_drain_weighted_scores_by_owed_magnitude():
    """Unlike the binary dodge, a replica owing FEW blocks outranks one
    owing many — even when the big debtor is less loaded."""
    broker, sinks = _mk_async(24, [("a", 2), ("b", 12), ("c", 8)],
                              loads={"a": 9, "b": 0, "c": 4})
    broker.request_grant("a", 16)              # free 2 -> order 12 b, 2 c
    owed_b = broker.open_order_units("b")
    owed_c = broker.open_order_units("c")
    assert owed_b > owed_c > 0                 # b idlest -> biggest order
    engines = {"b": _FakeEngine(0), "c": _FakeEngine(4)}
    r = Router("drain_weighted", broker=broker)
    # the binary dodge ties b and c (both draining) and takes b by load;
    # weighted scoring prefers c, the smaller debtor
    assert r.route(_req(), engines) == "c"
    assert r.drain_avoided == 1
    # drain the orders: pure load order returns (b wins again)
    for rid in ("b", "c"):
        for o in sinks[rid]:
            broker.fulfill_order(o.order_id, o.remaining)
    broker.check_invariants()
    assert r.route(_req(), engines) == "b"
    assert r.drain_avoided == 1


def test_drain_weighted_tiers_warm_then_local_then_remote():
    sched = _mk_fleet({"h0": 8, "h1": 8}, pool_units=4)
    sched.brokers["h0"].register("a", 2)
    sched.brokers["h1"].register("b", 2)
    sched.placements.update({"a": "h0", "b": "h1"})
    assert sched.brokers["h1"].snapshot_put("cnn", units=1,
                                            payload=object())
    r = Router("drain_weighted", fleet=sched)
    # tier 0: the warm row wins even on the most loaded replica
    engines = {"a": _FakeEngine(0), "b": _FakeEngine(9, warm=("cnn",))}
    assert r.route(_req(), engines) == "b"
    assert r.warm_routes == 1
    # tier 1: no warm row anywhere -> the replica co-hosted with the
    # snapshot wins (local restore), despite higher load
    engines = {"a": _FakeEngine(0), "b": _FakeEngine(5)}
    assert r.route(_req(), engines) == "b"
    assert r.snapshot_routes == 1
    # tier 2: snapshot only on a host with no candidate replica -> remote
    # for every candidate; load decides, the migration hook localizes
    engines = {"a": _FakeEngine(3)}
    assert r.route(_req(), engines) == "a"
    assert r.remote_routes == 1
    # tier 3: nothing cached anywhere -> plain least-loaded, uncounted
    engines = {"a": _FakeEngine(3), "b": _FakeEngine(1)}
    assert r.route(_req("html"), engines) == "b"
    assert (r.warm_routes, r.snapshot_routes, r.remote_routes) == (1, 1, 1)


def test_drain_avoided_counted_under_snapshot_affinity():
    """The accounting fix: snapshot_affinity's dodge of a mid-reclaim
    victim now increments ``drain_avoided`` (it used to count only under
    power_of_two)."""
    broker, sinks = _mk_async(8, [("a", 2), ("b", 6)], pool_units=8)
    broker.request_grant("b", 3)               # a is now draining
    broker.release_units("b", 2)
    assert broker.snapshot_put("cnn", units=1, payload=object())
    engines = {"a": _FakeEngine(0), "b": _FakeEngine(5)}
    r = Router("snapshot_affinity", broker=broker)
    assert r.route(_req(), engines) == "b"     # dodged the less-loaded a
    assert r.drain_avoided == 1
    assert r.snapshot_routes == 1


# ----------------------------------------------- (d) FleetSim / ClusterSim


def _stub_script(sim_cls, **kw):
    """One deterministic stub schedule (requester grant + victim drain +
    decode overlap) run through the given sim class; returns the full
    event history per replica + metrics."""
    broker = HostMemoryBroker(16, async_reclaim=True, clock=_fake_clock())
    a = StubReplica("a", broker, units=4, decode_steps=10)
    b = StubReplica("b", broker, units=12)
    g = a.request(8)
    assert g.pending == 8
    reqs = [Request(rid="r0", profile=PROFILES["cnn"], submit_s=0.0),
            Request(rid="r1", profile=PROFILES["bert"], submit_s=2.0)]
    sim = sim_cls({"a": a, "b": b}, broker=broker, **kw)
    m = sim.run(reqs, max_virtual_s=100)
    broker.check_invariants()
    return {"a": a.events, "b": b.events}, m


def test_fleetsim_single_host_replays_clustersim_stub_schedule():
    """The refactor seam, fast: FleetSim with one host produces the
    identical event history and metrics as ClusterSim on the same
    scripted stub schedule."""

    def cluster(engines, broker):
        return ClusterSim(engines, Router("least_loaded"), broker)

    def fleet(engines, broker):
        return FleetSim({"host0": engines}, Router("least_loaded"),
                        brokers={"host0": broker})

    ev_c, m_c = _stub_script(lambda engines, broker=None:
                             cluster(engines, broker))
    ev_f, m_f = _stub_script(lambda engines, broker=None:
                             fleet(engines, broker))
    assert ev_c == ev_f                        # full event histories
    m_c.pop("per_replica"), m_f.pop("per_replica")
    m_c.pop("broker"), m_f.pop("broker")
    assert m_c == m_f


def test_fleetsim_migrates_at_route_time_with_conservation():
    """Two stub hosts: an arrival pinned to host h0 whose pool lacks the
    snapshot pulls it over from h1 at route time; per-host conservation
    holds after every tick (stubs check their broker each tick) and the
    fleet metrics surface the migration."""
    sched = FleetScheduler(bandwidth_bytes_per_s=1024.0,
                           link_latency_s=0.5)
    b0 = HostMemoryBroker(16, async_reclaim=True, clock=_fake_clock(),
                          snapshot_pool_units=4)
    b1 = HostMemoryBroker(16, async_reclaim=True, clock=_fake_clock(),
                          snapshot_pool_units=4)
    sched.add_host("h0", b0)
    sched.add_host("h1", b1)
    a = StubReplica("a", b0, units=4)
    b = StubReplica("b", b1, units=4)
    assert b1.snapshot_put("cnn", units=2, nbytes=512, payload=object(),
                           replica_id="b")
    sched.check_invariants()
    sim = FleetSim({"h0": {"a": a}, "h1": {"b": b}},
                   Router(route_fn=lambda r, e: "a"), scheduler=sched)
    m = sim.run([Request(rid="r0", profile=PROFILES["cnn"], submit_s=0.0)],
                max_virtual_s=100)
    sched.check_invariants()
    assert m["completed"] == 1
    assert m["snapshot_migrations"] == 1
    assert m["fleet"]["migrations"] == 1
    assert b0.snapshot_restorable("cnn")       # localized at route time
    assert not b1.snapshot_available("cnn")
    rec = sched.migrations[0]
    assert (rec.src, rec.dst) == ("h1", "h0")
    assert rec.copy_seconds == pytest.approx(0.5 + 512 / 1024.0)
    # stamped on the fleet clock: routed at t=0, before any tick advanced
    assert rec.at == 0.0


def test_fleetsim_no_migration_for_warm_target():
    """The route-time hook skips the copy when the chosen replica holds a
    warm row — an adopt beats any restore, the transfer would be waste."""
    sched = FleetScheduler(clock=_fake_clock())
    b0 = HostMemoryBroker(16, clock=_fake_clock(), snapshot_pool_units=4)
    b1 = HostMemoryBroker(16, clock=_fake_clock(), snapshot_pool_units=4)
    sched.add_host("h0", b0)
    sched.add_host("h1", b1)
    a = StubReplica("a", b0, units=4, decode_steps=2)
    b = StubReplica("b", b1, units=4)
    a.warm["cnn"] = [(0.0, "w0", 0)]
    assert b1.snapshot_put("cnn", units=2, payload=object())
    sim = FleetSim({"h0": {"a": a}, "h1": {"b": b}},
                   Router(route_fn=lambda r, e: "a"), scheduler=sched)
    sim.run([Request(rid="r0", profile=PROFILES["cnn"], submit_s=0.0)],
            max_virtual_s=50)
    assert not sched.migrations                # warm target: no copy
    assert b1.snapshot_available("cnn")


def test_metrics_p99_hardening():
    """latency_p99 is None (not a 1-sample numpy percentile) until at
    least 2 requests completed; p50 appears from the first completion."""
    broker = HostMemoryBroker(16, clock=_fake_clock())
    a = StubReplica("a", broker, units=4, decode_steps=3)
    sim = ClusterSim({"a": a}, Router(route_fn=lambda r, e: "a"), broker)
    m = sim.run([Request(rid="r0", profile=PROFILES["cnn"], submit_s=0.0)],
                max_virtual_s=50)
    assert m["completed"] == 1
    assert m["latency_p50"] is not None
    assert m["latency_p99"] is None            # 1 sample: no tail stat
    a2 = StubReplica("a2", HostMemoryBroker(16, clock=_fake_clock()),
                     units=4, decode_steps=3)
    sim2 = ClusterSim({"a2": a2}, Router(route_fn=lambda r, e: "a2"))
    m2 = sim2.run([Request(rid="q0", profile=PROFILES["cnn"], submit_s=0.0),
                   Request(rid="q1", profile=PROFILES["cnn"], submit_s=0.0)],
                  max_virtual_s=50)
    assert m2["completed"] == 2
    assert isinstance(m2["latency_p99"], float)


# --------------------------------------------- engine integration (slow)


@pytest.fixture(scope="module")
def setup():
    import jax
    from repro.configs.base import get_config, reduced
    from repro.core.arena import ArenaSpec
    from repro.models import model as M
    cfg = reduced(get_config("qwen2-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = ArenaSpec.from_model(cfg, partition_tokens=128, n_partitions=8,
                                block_tokens=32)
    return cfg, params, spec


def _fleet_reqs():
    from repro.serving.tracegen import assign_profiles, bursty_trace
    quiet = bursty_trace(6.0, 0.9, burst_x=1.0, burst_len=0.0, seed=2)
    burst = [4.0 + t for t in bursty_trace(4.0, 3.0, burst_x=3.0,
                                           burst_at=(0.0,), burst_len=2.0,
                                           seed=3)]
    reqs = [Request(rid=f"b{i}", profile=p, submit_s=t)
            for i, (t, p) in enumerate(assign_profiles(quiet, PROFILES, 2))]
    reqs += [Request(rid=f"a{i}", profile=p, submit_s=t)
             for i, (t, p) in enumerate(assign_profiles(burst, PROFILES, 3))]
    return reqs


class _FakeClock:
    def __init__(self, step=1e-4):
        self._t = 0.0
        self._step = step

    def perf_counter(self):
        self._t += self._step
        return self._t


@pytest.mark.slow
def test_fleetsim_one_host_stepevent_trace_bit_identical(setup,
                                                         monkeypatch):
    """THE seam regression: a contended two-replica trace (steals, async
    orders, routing) produces a bit-identical StepEvent trace — every
    (t, kind, wall, detail) tuple on every replica — through ClusterSim
    and through FleetSim with that one host."""
    import repro.core.elastic as elastic_mod
    import repro.core.hotmem as hotmem_mod
    import repro.core.vanilla as vanilla_mod
    import repro.serving.engine as engine_mod
    from repro.serving.engine import ServeEngine
    cfg, params, spec = setup
    bpp = spec.blocks_per_partition

    def run(mk_sim):
        clock = _FakeClock()
        for mod in (engine_mod, elastic_mod, hotmem_mod, vanilla_mod):
            monkeypatch.setattr(mod, "time", clock)
        broker = HostMemoryBroker(budget_units=10 * bpp,
                                  async_reclaim=True)
        engines = {rid: ServeEngine(cfg, params, spec, mode="hotmem",
                                    keep_alive=3.0, seed=i, broker=broker,
                                    replica_id=rid)
                   for i, rid in enumerate(("A", "B"))}
        sim = mk_sim(engines, broker)
        m = sim.run(_fleet_reqs(), max_virtual_s=2000)
        broker.check_invariants()
        traces = {rid: [(e.t, e.kind, e.wall_s, e.detail)
                        for e in eng.events]
                  for rid, eng in engines.items()}
        return traces, m

    t_c, m_c = run(lambda engines, broker:
                   ClusterSim(engines, Router("power_of_two"), broker))
    t_f, m_f = run(lambda engines, broker:
                   FleetSim({"host0": engines}, Router("power_of_two"),
                            brokers={"host0": broker}))
    assert t_c == t_f
    assert m_c["completed"] == m_f["completed"] > 0
    assert m_c["routed"] == m_f["routed"]
    assert m_c["broker"]["steals"] == m_f["broker"]["steals"] > 0


@pytest.mark.slow
def test_fleet_migration_ttft_ordering(setup):
    """The fleet acceptance property, measured: across a 2-host fleet
    the remote-migrated restore's TTFT lands strictly between the local
    restore and the cold prefill.  Medians of 3 samples per path (the
    same cycles the ``fleet_migration`` benchmark rows report) — a
    single-shot restore wall is noise-dominated on a busy CPU."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.figures import _fleet_migration_medians
    cfg, params, spec = setup
    (local_us, remote_us, cold_us), sched, A = _fleet_migration_medians(
        cfg, params, spec, repeats=3)
    assert A.remote_restore_starts == 3 and len(sched.migrations) == 3
    copy_us = sched.migrations[-1].copy_seconds * 1e6
    assert local_us < remote_us < cold_us, \
        (local_us, remote_us, cold_us, copy_us)


@pytest.mark.slow
def test_fleet_remote_restore_end_to_end(setup):
    """Capture on host B, migrate, restore on host A: the restore event
    is tagged ``source="remote"`` with the origin host and the modeled
    copy charge, the engine counts it, per-host conservation holds, and
    the copy is paid exactly once."""
    from repro.serving.engine import ServeEngine
    cfg, params, spec = setup
    bpp = spec.blocks_per_partition
    sched = FleetScheduler()                   # default bandwidth/latency
    bA = HostMemoryBroker(budget_units=12 * bpp,
                          snapshot_pool_units=4 * bpp)
    bB = HostMemoryBroker(budget_units=12 * bpp,
                          snapshot_pool_units=4 * bpp)
    sched.add_host("h0", bA)
    sched.add_host("h1", bB)
    A = ServeEngine(cfg, params, spec, mode="hotmem", keep_alive=2.0,
                    seed=0, broker=bA, replica_id="A")
    B = ServeEngine(cfg, params, spec, mode="hotmem", keep_alive=2.0,
                    seed=1, broker=bB, replica_id="B")
    sched.placements.update({"A": "h0", "B": "h1"})
    empty = deque()

    def run_one(eng, rid, prof="cnn"):
        eng.submit(Request(rid=rid, profile=PROFILES[prof],
                           submit_s=eng.now))
        while eng.active or eng.pending:
            eng._tick(empty)
        req = next(r for r in eng.done if r.rid == rid)
        return (req.first_token_s - req.admitted_s)

    # A: a LOCAL restore first (own capture after expiry), for the tag
    run_one(A, "jit0")
    for prof, entries in list(A.warm.items()):
        for (_, rid, _row) in entries:
            A.arena.finish(rid)
        A.warm[prof] = []
    run_one(A, "c0")
    A.now += A.keep_alive + 1.0
    A._recycle_idle()                          # capture cnn on h0
    sched.check_invariants()
    run_one(A, "s0")
    assert A.restore_starts == 1 and A.remote_restore_starts == 0
    local_ev = [e for e in A.events if e.kind == "restore"][-1]
    assert local_ev.detail["source"] == "local"
    bA.snapshot_drop("cnn")                    # forget, for the remote run
    sched.check_invariants()

    # B runs bert, captures it on h1 — A has never seen bert's KV
    run_one(B, "jitB", prof="bert")
    B.now += B.keep_alive + 1.0
    B._recycle_idle()
    sched.check_invariants()
    assert bB.snapshot_restorable("bert")
    assert not bA.snapshot_available("bert")

    rec = sched.ensure_local("bert", "h0")     # the fleet migration
    sched.check_invariants()
    assert rec is not None and rec.copy_seconds > 0
    assert not bB.snapshot_available("bert")

    # A's expired warm row for cnn is gone; admit bert -> REMOTE restore
    A.now += A.keep_alive + 1.0
    A._recycle_idle()
    run_one(A, "r0", prof="bert")
    sched.check_invariants()
    assert A.remote_restore_starts == 1 and A.restore_starts == 2
    ev = [e for e in A.events if e.kind == "restore"][-1]
    assert ev.detail["source"] == "remote"
    assert ev.detail["origin"] == "h1"
    assert ev.detail["copy_s"] == pytest.approx(rec.copy_seconds)
    assert ev.wall_s >= rec.copy_seconds       # the copy was charged
    # measured and modeled kept apart: the modeled part is the copy
    assert ev.modeled_s == pytest.approx(rec.copy_seconds)
    assert ev.wall_s == pytest.approx(ev.host_s + ev.modeled_s)
    # paid once: a second restore of the now-local entry is local again
    A.now += A.keep_alive + 1.0
    A._recycle_idle()
    run_one(A, "r1", prof="bert")
    assert A.remote_restore_starts == 1 and A.restore_starts == 3
    assert [e for e in A.events if e.kind == "restore"][-1] \
        .detail["source"] == "local"


def test_migration_preserves_tenant_attribution():
    """A cross-host snapshot migration keeps the entry's OWNER tenant:
    the source ledger credits and the destination ledger charges the
    same tenant account, and the destination host's protection rule
    covers the migrated entry exactly as a local capture."""
    sched = FleetScheduler()
    bA = HostMemoryBroker(8, async_reclaim=True, snapshot_pool_units=4,
                          tenants={"a": 4, "b": 4}, clock=_fake_clock())
    bB = HostMemoryBroker(8, async_reclaim=True, snapshot_pool_units=4,
                          tenants={"a": 4, "b": 4}, clock=_fake_clock())
    sched.add_host("h0", bA)
    sched.add_host("h1", bB)
    assert bB.snapshot_put("fn", units=2, payload=("kv", "fn"),
                           nbytes=256, tenant="a")
    assert bB.ledger.tenant_snapshot("a") == 2
    sched.check_invariants()

    rec = sched.ensure_local("fn", "h0")
    assert rec is not None and rec.copy_seconds > 0
    snap = bA.snapshots.peek("fn")
    assert snap is not None and snap.tenant == "a"   # owner travelled
    assert bA.ledger.tenant_snapshot("a") == 2
    assert bB.ledger.tenant_snapshot("a") == 0
    sched.check_invariants()

    # on the destination, tenant b's pressure cannot squeeze it: a's
    # usage there (2) is already below a's sub-budget (4)
    bA.register("vb", 3, load=lambda: 0, tenant="b", mode="model")
    g = bA.request_grant("vb", 5)                    # free 3 + deficit 2
    assert g.granted == 3
    assert bA.squeeze_log == []
    assert bA.snapshots.peek("fn") is not None
    sched.check_invariants()
