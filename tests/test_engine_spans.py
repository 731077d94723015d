"""Engine host spans and step stamps.

Every phase of ``ServeEngine._tick`` is a ``jax.profiler.TraceAnnotation``
named ``engine.<phase>`` and is stamped with ``time.perf_counter``; a decode
event carries the host self time of each phase of the tick that ran it.
The properties pinned down: the step's wall is tiled by its launch and its
wait, self times are non-negative and fit in the tick, a tick without
device work logs nothing, the spans land on the profiler's host plane under
names the benchmark's idle attribution does not claim, the executables have
stable names, and a restore keeps its measured wall apart from its modeled
part.
"""
import re
import sys
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import HostMemoryBroker
from repro.core.arena import ArenaSpec
from repro.serving.request import PROFILES, Request

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.tracing import HOST_SPANS  # noqa: E402

PHASES = {"grants", "orders", "admit", "inputs", "launch", "wait", "post",
          "recycle", "resize"}


@pytest.fixture(scope="module")
def setup():
    from repro.configs.base import get_config, reduced
    from repro.models import model as M
    cfg = reduced(get_config("qwen2-7b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = ArenaSpec.from_model(cfg, partition_tokens=128, n_partitions=8,
                                block_tokens=32)
    return cfg, params, spec


def _engine(setup, **kw):
    from repro.serving.engine import ServeEngine
    cfg, params, spec = setup
    kw.setdefault("keep_alive", 1e9)
    kw.setdefault("prewarm", False)
    return ServeEngine(cfg, params, spec, mode="hotmem", seed=0, **kw)


def _submit(eng, n, prof="cnn"):
    for i in range(n):
        eng.submit(Request(rid=f"{prof}{i}", profile=PROFILES[prof],
                           submit_s=0.0))


def _ticks(eng, n):
    """Run ``n`` ticks; returns (tick wall, events the tick appended)."""
    out = []
    for _ in range(n):
        before = len(eng.events)
        t0 = time.perf_counter()
        eng._tick(deque())
        out.append((time.perf_counter() - t0, eng.events[before:]))
    return out


@pytest.fixture(scope="module")
def served(setup):
    eng = _engine(setup)
    _submit(eng, 3)
    return eng, _ticks(eng, 6)


@pytest.mark.slow
def test_launch_and_wait_tile_the_decode_wall(served):
    eng, _ticked = served
    decodes = [ev for ev in eng.events if ev.kind == "decode"]
    assert len(decodes) == 6
    for ev in decodes:
        assert set(ev.phases) == PHASES
        assert all(v >= 0.0 for v in ev.phases.values())
        assert ev.phases["launch"] + ev.phases["wait"] == pytest.approx(
            ev.wall_s, rel=1e-9, abs=1e-12)
        assert ev.host_s == pytest.approx(ev.wall_s, rel=1e-9, abs=1e-12)
        assert ev.modeled_s == 0.0
    # one dict per tick, not one shared by every step
    assert len({id(ev.phases) for ev in decodes}) == len(decodes)


@pytest.mark.slow
def test_self_time_fits_in_the_tick(served):
    eng, ticked = served
    for wall, evs in ticked:
        decode = [ev for ev in evs if ev.kind == "decode"]
        assert len(decode) == 1
        others = sum(ev.host_s for ev in evs if ev.kind != "decode")
        assert sum(decode[0].phases.values()) + others <= wall
    # the first tick prefilled two cold starts (the arena starts at two
    # rows) inside its admit phase and grew the arena inside its resize
    # phase: their walls are not the phases' self time
    first = ticked[0][1]
    kinds = [ev.kind for ev in first]
    assert kinds.count("prefill") == 2 and "plug" in kinds
    prefills = sum(ev.host_s for ev in first if ev.kind == "prefill")
    assert first[-1].kind == "plug"
    decode = next(ev for ev in first if ev.kind == "decode")
    assert decode.phases["admit"] < prefills
    assert all(ev.start <= ev.end for ev in first)


@pytest.mark.slow
def test_tick_without_device_work_logs_nothing(setup):
    eng = _engine(setup)
    later = deque([Request(rid="x", profile=PROFILES["cnn"],
                           submit_s=5.0)])
    eng._tick(later)                         # only a future arrival
    assert len(later) == 1 and eng.now == 5.0
    eng._tick(deque())                       # nothing to serve
    assert eng.now > 5.0
    assert eng.events == []


@pytest.mark.slow
def test_spans_on_the_profiler_host_plane(setup, tmp_path):
    from jax.profiler import ProfileData
    eng = _engine(setup)
    _submit(eng, 3)
    eng._tick(deque())                       # compile outside the trace
    _submit(eng, 1, prof="bert")
    with jax.profiler.trace(str(tmp_path)):
        _ticks(eng, 2)
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    spans.append((line.name, ev.start_ns, ev.end_ns,
                                  ev.name, dict(ev.stats)))
    names = {s[3] for s in spans}
    assert {"engine.grants", "engine.orders", "engine.admit",
            "engine.prefill", "engine.decode", "engine.decode.inputs",
            "engine.decode.launch", "engine.decode.wait",
            "engine.decode.post", "engine.recycle", "engine.resize",
            "engine.plug"} <= names
    assert not any(HOST_SPANS.match(n) for n in names)
    # every wait lies inside a decode span on its thread's line
    decodes = [s for s in spans if s[3] == "engine.decode"]
    for line, a, b, _n, _st in (s for s in spans
                                if s[3] == "engine.decode.wait"):
        assert any(ln == line and a0 <= a and b <= b0
                   for ln, a0, b0, _n, _st in decodes)
    plug = next(s for s in spans if s[3] == "engine.plug")
    assert any(s[3] == "engine.resize" and s[1] <= plug[1]
               and plug[2] <= s[2] for s in spans)
    rids = {s[4]["rid"] for s in spans if s[3] == "engine.prefill"}
    assert rids == {"cnn2", "bert0"}
    admits = [(st["scanned"], st["admitted"]) for _l, _a, _b, n, st in spans
              if n == "engine.admit"]
    assert admits == [(2, 2), (0, 0)]


@pytest.mark.slow
def test_executables_have_stable_names(setup):
    from repro.models import model as M
    cfg, params, _spec = setup
    eng = _engine(setup)
    _submit(eng, 1)
    eng._tick(deque())
    rows = eng._rows()
    text = eng._decode_jit[rows].lower(
        params, jnp.zeros((rows, 1), jnp.int32),
        jnp.zeros((rows,), jnp.int32), eng.caches).as_text()
    assert re.search(r"module @jit_decode_step\b", text)
    n = PROFILES["cnn"].prompt_tokens
    text = eng._prefill_jit[n].lower(
        params, jnp.zeros((1, n), jnp.int32),
        M.init_caches(cfg, 1, eng.spec.partition_tokens)).as_text()
    assert re.search(r"module @jit_prefill\b", text)


@pytest.mark.slow
def test_restore_keeps_measured_and_modeled_walls_apart(setup):
    """A restore's charge is its measured host wall plus a modeled part:
    a replica that maps every page already is charged nothing for the
    row write it measured; one that copies every page is charged what
    it measured."""
    _cfg, _params, spec = setup
    bpp = spec.blocks_per_partition
    broker = HostMemoryBroker(budget_units=12 * bpp,
                              snapshot_pool_units=4 * bpp)
    a = _engine(setup, keep_alive=2.0, broker=broker, replica_id="A",
                snapshot_page_bytes=4096)
    b = _engine(setup, keep_alive=2.0, broker=broker, replica_id="B",
                snapshot_page_bytes=4096)

    def run_one(eng, rid):
        eng.submit(Request(rid=rid, profile=PROFILES["cnn"],
                           submit_s=eng.now))
        while eng.active or eng.pending:
            eng._tick(deque())

    run_one(a, "c0")
    a.now += a.keep_alive + 1.0
    a._recycle_idle()                        # capture on expiry
    snap = next(ev for ev in a.events if ev.kind == "snapshot")
    assert snap.host_s == pytest.approx(snap.wall_s, rel=1e-9, abs=1e-12)
    run_one(b, "r0")                         # every page new to B
    run_one(a, "r1")                         # every page mapped on A
    ev_b = next(ev for ev in b.events if ev.kind == "restore")
    ev_a = next(ev for ev in a.events if ev.kind == "restore")
    assert ev_b.detail["pages_shared"] == 0
    assert ev_a.detail["pages_shared"] == ev_a.detail["pages_total"]
    for ev in (ev_a, ev_b):
        assert ev.host_s > 0.0
        assert ev.wall_s == pytest.approx(ev.host_s + ev.modeled_s,
                                          rel=1e-9, abs=1e-12)
    assert ev_b.modeled_s == 0.0 and ev_b.wall_s == ev_b.host_s
    assert ev_a.wall_s == 0.0
    assert ev_a.modeled_s == pytest.approx(-ev_a.host_s)


def test_phase_self_time_excludes_nested_steps():
    from repro.serving.engine import StepEvent, _covered_s, _Phase
    events, phases = [], {}
    with _Phase(events, phases, "orders"):
        t = time.perf_counter()
        events.append(StepEvent(0.0, "unplug", 0.0, start=t - 10.0,
                                end=t + 10.0))
    assert phases == {"orders": 0.0}
    evs = [StepEvent(0.0, "plug", 0.0, start=1.0, end=4.0),
           StepEvent(0.0, "stall", 0.0, start=2.0, end=3.0),
           StepEvent(0.0, "unplug", 0.0, start=6.0, end=12.0)]
    assert _covered_s(evs, 0.0, 10.0) == pytest.approx(7.0)
    assert _covered_s([], 0.0, 10.0) == 0.0
    assert np.isclose(_covered_s(evs[:2], 2.5, 3.5), 1.0)
