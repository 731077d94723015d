"""The readers of the engine's step spans and of the decode executable's
device time, on synthetic views: ``decode_device_ms`` counts only the
``decode_step`` executables, ``decode_launch_ms`` and ``tick_host_ms`` read
the phases of the window's decode events, and each reads nothing (``None``)
where its source is absent, as on a program without the spans."""
import dataclasses
import types

import pytest

from bench import run as R
from bench.tracing import TraceSummary
from repro.serving.engine import StepEvent

CELL = "qwen2-1.5b.saturate"
NAMES = ["decode_device_ms.saturate", "decode_launch_ms.saturate",
         "tick_host_ms.saturate"]


def _phases(launch, wait, **host):
    return {"grants": 0.0, "orders": 0.0, "admit": 0.0, "inputs": 0.0,
            "post": 0.0, "recycle": 0.0, "resize": 0.0, **host,
            "launch": launch, "wait": wait}


def _decode(launch, wait, **host):
    return StepEvent(0.0, "decode", launch + wait, {"batch": 1},
                     start=1.0, end=1.0 + launch + wait,
                     phases=_phases(launch, wait, **host))


@dataclasses.dataclass
class OldStepEvent:
    """A step event of a program without spans: no stamps, no phases."""
    t: float
    kind: str
    wall_s: float
    detail: dict


def _view(events, span, trace=None):
    """A ``RunView`` over one replica whose window holds ``events[a:b]``."""
    cj = {"partition_tokens": 16}
    cell = types.SimpleNamespace(
        engines={"S": types.SimpleNamespace(events=events)}, cj=cj,
        ref=types.SimpleNamespace(row_bytes=lambda cj, t: 1),
        spec=types.SimpleNamespace(partition_tokens=16),
        driver=types.SimpleNamespace(decodes=[]))
    win = types.SimpleNamespace(seconds=1.0, event_span={"S": span},
                                decode_span=(0, 0), records=[])
    return R.RunView(cell, win, trace, None)


def _read(name, view):
    return R.metric_reader(name)(view)


def _trace(executables):
    return TraceSummary(chips=1, window_s=1.0, busy_s=0.5,
                        executables=executables, top_ops=[], idle_gaps=[])


def test_entries_name_these_readers():
    entries = {m["name"]: m for m in R.load_benchmark()["per_layer"]}
    for name in NAMES:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["unit"] == "ms"
        R.metric_reader(name)


def test_device_time_counts_only_the_decode_step():
    view = _view([], (0, 0), _trace({
        "jit_decode_step": [4, 0.1],
        "jit_prefill": [2, 5.0],
        "jit__greedy": [4, 0.004]}))
    assert _read("decode_device_ms.saturate", view) == pytest.approx(25.0)


def test_device_time_needs_a_traced_decode_step():
    assert _read("decode_device_ms.saturate", _view([], (0, 0))) is None
    old = _view([], (0, 0), _trace({"jit__lambda": [4, 0.1],
                                    "jit_prefill": [2, 5.0]}))
    assert _read("decode_device_ms.saturate", old) is None


def test_phases_of_window_decodes_only():
    before = _decode(0.5, 0.5, admit=0.9)            # outside the window
    inside = [_decode(0.002, 0.026, admit=0.0004, inputs=0.0001,
                      post=0.0002),
              StepEvent(0.0, "prefill", 0.2, {"rid": "x"}, start=2.0,
                        end=2.2),
              _decode(0.004, 0.024, grants=0.0001, recycle=0.0003,
                      resize=0.0001)]
    after = _decode(0.5, 0.5, post=0.9)              # outside the window
    view = _view([before] + inside + [after], (1, 4))
    assert _read("decode_launch_ms.saturate", view) == pytest.approx(3.0)
    assert _read("tick_host_ms.saturate", view) == pytest.approx(0.6)


def test_phases_read_nothing_without_spans():
    empty = _view([], (0, 0))
    old = _view([OldStepEvent(0.0, "decode", 0.03, {"batch": 1}),
                 OldStepEvent(0.0, "prefill", 0.2, {"rid": "x"})], (0, 2))
    no_decode = _view([StepEvent(0.0, "plug", 0.01, {"units": 1},
                                 start=0.0, end=0.01)], (0, 1))
    for view in (empty, old, no_decode):
        assert _read("decode_launch_ms.saturate", view) is None
        assert _read("tick_host_ms.saturate", view) is None
