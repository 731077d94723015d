"""The reduction from a profiler trace to busy time, executable times and
idle gaps, on a small trace recorded on a TPU v5e (the harness at the
tiny test widths, one traced second; ``data/small.json`` holds what the
recording run read from it)."""
import json
from pathlib import Path

import pytest

from bench import tracing

DATA = Path(__file__).with_name("data")


@pytest.fixture(scope="module")
def recorded():
    want = json.loads((DATA / "small.json").read_text())
    return want, tracing.reduce_trace(DATA / "small.xplane.pb",
                                      want["window_s"])


def test_reads_what_the_recording_run_read(recorded):
    want, got = recorded
    assert got.chips == 1
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < got.busy_s < got.window_s


def test_idle_gaps_cover_the_idle_time(recorded):
    _want, got = recorded
    idle = sum(s for _n, s in got.idle_gaps)
    assert idle == pytest.approx(got.window_s - got.busy_s, rel=1e-6)
    names = {n for n, _s in got.idle_gaps}
    assert names <= {"route", "wait", "tick.A", "tick.B", "unattributed"}
    assert names & {"tick.A", "tick.B"}


def test_executables_and_ops(recorded):
    _want, got = recorded
    assert sum(s for _n, s in got.executables.values()) == pytest.approx(
        got.busy_s, rel=0.05)
    assert any("decode" in k or "lambda" in k for k in got.executables)
    assert got.top_ops and all(s > 0 for _n, s in got.top_ops)
    assert [s for _n, s in got.top_ops] == sorted(
        (s for _n, s in got.top_ops), reverse=True)


def test_merge_and_gaps():
    assert tracing._merge([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert tracing._gaps([[2, 3], [5, 6]], 0, 10) == [(0, 2), (3, 5),
                                                      (6, 10)]
    spans = [(0, 4, "tick.A"), (4, 10, "wait")]
    got = dict(tracing._attribute([(2, 6)], spans))
    assert got == {"tick.A": 2e-9, "wait": 2e-9}
