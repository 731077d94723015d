"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the breakdown read.

- busy: the union of the intervals in which an executable ran on a chip
  (the ``XLA Modules`` line of each TPU plane), averaged over chips;
- executables: device seconds and launches per executable name, so a
  kernel's share of its roofline is read from the whole executable that
  implements it, whatever is inside;
- top device ops (``XLA Ops`` line) by total seconds;
- idle gaps: each stretch of the window in which no executable ran,
  attributed to the host span (``route``, ``tick.<replica>``, ``wait``)
  that covers it, summed by span name.

Times are taken inside ``[origin, origin + window_s]``, where the origin is
the start of the ``window`` span that ``bench/driver.py`` records on the host.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path

HOST_SPANS = re.compile(r"^(route|wait|tick\.[\w.-]+)$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float                       # mean over chips
    executables: dict                   # name -> [launches, seconds]
    top_ops: list                       # [[name, seconds], ...]
    idle_gaps: list                     # [[host span, seconds], ...]


def find_trace(root: Path) -> Path:
    found = sorted(Path(root).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def _base(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _merge(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(iv: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in iv if b > lo and a < hi]


def reduce_trace(path, window_s: float, top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    spans, origin = [], None
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "window" and origin is None:
                    origin = ev.start_ns
                elif HOST_SPANS.match(ev.name):
                    spans.append((ev.start_ns, ev.end_ns, ev.name))
    lo_all = []
    mods_by_chip, ops = [], defaultdict(float)
    execs: dict = defaultdict(lambda: [0, 0.0])
    for plane in devices:
        iv = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    iv.append([ev.start_ns, ev.end_ns])
                    lo_all.append(ev.start_ns)
        mods_by_chip.append((plane, iv))
    if origin is None:
        origin = min(lo_all) if lo_all else 0.0
    hi = origin + window_s * 1e9
    for plane in devices:
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    if ev.end_ns > origin and ev.start_ns < hi:
                        ops[ev.name] += ev.duration_ns
    busy = []
    for plane, iv in mods_by_chip:
        busy.append(_merge(_clip(iv, origin, hi)))
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                if ev.end_ns > origin and ev.start_ns < hi:
                    e = execs[_base(ev.name)]
                    e[0] += 1
                    e[1] += (min(ev.end_ns, hi) - max(ev.start_ns, origin)) \
                        / 1e9
    busy_s = sum(sum(b - a for a, b in m) for m in busy) / 1e9 / len(busy)
    gaps = _gaps(busy[0], origin, hi)
    return TraceSummary(
        chips=len(devices), window_s=window_s, busy_s=busy_s,
        executables={k: v for k, v in execs.items()},
        top_ops=[[k, v / 1e9] for k, v in
                 sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=_attribute(gaps, spans)[:top])


def _gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def _attribute(gaps: list, spans: list) -> list:
    """Idle device seconds under each host span name, largest first; idle
    time under no span is ``unattributed``."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    out: dict = defaultdict(float)
    for a, b in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(spans) and spans[i][0] < b:
            s0, s1, name = spans[i]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] += ov / 1e9
                covered += ov
            i += 1
        if b - a - covered > 0:
            out["unattributed"] += (b - a - covered) / 1e9
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])]
