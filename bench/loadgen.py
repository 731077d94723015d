"""One general generator of FaaS traffic, driven by a mix file.

A mix (``bench/traffic/<name>.json``) names its tenants, each tenant's
functions (a fixed system-prompt length and decode length per function,
ranked by popularity), and the arrival shape.  Open loop
(``alternating_bursts``), at the aggregate offered rate ``rate_per_s``:
one tenant at a time runs at ``burst_x`` times its base rate.
Closed loop (``closed``): ``clients`` requests are in flight at once, split
evenly over the tenants, and each one that finishes is followed at once by
its tenant's next, so the load is whatever the program can serve.
Popularity within a tenant is Zipf(``zipf_s``) over the function ranks.

Every seed gets the same work in another order.  Open loop: each tenant's
arrivals in each constant-rate segment are ``round(rate * length)`` times
drawn uniformly in the segment (a Poisson process conditioned on its
count), and each tenant's function picks are the largest-remainder Zipf
allocation of its arrival count, shuffled.  Closed loop: a tenant's
requests come in blocks of ``block`` picks, each block the same Zipf
allocation with each function's picks spaced evenly through it at a
seeded phase, so any run of requests holds the same mix to within one
block, at the same spacing.  Streams are seeded per name (a copy of
``repro.serving.tracegen.stream_seed``), so one tenant's draws never
depend on another's.
"""
from __future__ import annotations

import dataclasses
import json
import zlib
from pathlib import Path

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float              # seconds after the window opens
    tenant: str
    replica: str
    function: str
    prompt: int
    decode: int


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def stream_rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per named stream, stable across processes."""
    return np.random.default_rng(
        np.random.SeedSequence([seed, zlib.crc32(stream.encode())]))


def zipf_counts(n: int, k: int, s: float) -> list[int]:
    """Largest-remainder split of ``n`` picks over ``k`` ranks, Zipf(s)."""
    w = 1.0 / np.arange(1, k + 1) ** s
    q = n * w / w.sum()
    out = np.floor(q).astype(int)
    for i in np.argsort(-(q - out), kind="stable")[: n - int(out.sum())]:
        out[i] += 1
    return out.tolist()


def tenant_segments(mix: dict, ti: int, duration: float
                    ) -> list[tuple[float, float, float]]:
    """(start, end, rate) pieces of one tenant's offered rate."""
    arr = mix["arrivals"]
    n = len(mix["tenants"])
    total = float(mix["rate_per_s"])
    if arr["kind"] != "alternating_bursts":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    x, period = float(arr["burst_x"]), float(arr["period_s"])
    burst = period / n
    base = total / (x + n - 1)            # one tenant hot at any moment
    out, t = [], 0.0
    while t < duration:
        slot = int(round(t / burst)) % n
        end = min(t + burst, duration)
        out.append((t, end, base * x if slot == ti else base))
        t = end
    return out


class ClosedLoop:
    """Closed-loop clients: ``clients[tenant]`` requests of each tenant in
    flight at once; ``next(tenant)`` is the request that follows one that
    finished (its ``t`` is 0: it falls due when it is issued)."""

    def __init__(self, mix: dict, seed: int, stream: str):
        n = int(mix["arrivals"]["clients"])
        tenants = mix["tenants"]
        if n % len(tenants):
            raise ValueError(f"{n} clients do not split over "
                             f"{len(tenants)} tenants")
        self.clients = {t["name"]: n // len(tenants) for t in tenants}
        self._seqs = {t["name"]: self._blocks(mix, t, stream_rng(
            seed, f"{stream}/{t['name']}")) for t in tenants}

    @staticmethod
    def _blocks(mix: dict, ten: dict, rng: np.random.Generator):
        """Each function's picks spaced evenly through every block, at a
        phase drawn per function from the seed: the spacing, and so how
        long a warm container waits for its function's next request, is
        the same for every seed."""
        fns = ten["functions"]
        counts = zipf_counts(int(mix["arrivals"]["block"]), len(fns),
                             mix["zipf_s"])
        phase = rng.uniform(size=len(fns))
        slots = sorted((float((j + phase[i]) / k), i)
                       for i, k in enumerate(counts) for j in range(k))
        picks = [i for _t, i in slots]
        while True:
            for i in picks:
                f = fns[i]
                yield Arrival(0.0, ten["name"], ten["replica"], f["name"],
                              int(f["prompt"]), int(f["decode"]))

    def next(self, tenant: str) -> Arrival:
        return next(self._seqs[tenant])


def generate(mix: dict, seed: int, duration: float,
             stream: str = "window"):
    """The mix's arrivals in [0, duration), sorted by time; for a closed
    loop, its ``ClosedLoop``."""
    if mix["arrivals"]["kind"] == "closed":
        return ClosedLoop(mix, seed, stream)
    out: list[Arrival] = []
    for ti, ten in enumerate(mix["tenants"]):
        rng = stream_rng(seed, f"{stream}/{ten['name']}")
        times: list[float] = []
        for a, b, rate in tenant_segments(mix, ti, duration):
            k = int(round(rate * (b - a)))
            times.extend(np.sort(rng.uniform(a, b, size=k)).tolist())
        fns = ten["functions"]
        picks = np.repeat(np.arange(len(fns)),
                          zipf_counts(len(times), len(fns), mix["zipf_s"]))
        rng.shuffle(picks)
        for t, i in zip(times, picks):
            f = fns[int(i)]
            out.append(Arrival(t, ten["name"], ten["replica"], f["name"],
                               int(f["prompt"]), int(f["decode"])))
    out.sort(key=lambda a: (a.t, a.tenant))
    return out


def cold_sweep(mix: dict) -> list[Arrival]:
    """Warm-up opener: each replica cold-starts every prompt length its
    tenants use once, so no prefill program compiles later."""
    out = []
    for ten in mix["tenants"]:
        seen = set()
        for f in ten["functions"]:
            if f["prompt"] not in seen:
                seen.add(f["prompt"])
                out.append(Arrival(0.0, ten["name"], ten["replica"],
                                   f["name"], int(f["prompt"]),
                                   int(f["decode"])))
    return out
