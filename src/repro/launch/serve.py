"""Serving launcher: trace-driven elastic serving on any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --mode hotmem --duration 20 --rate 1.0

Runs the ServeEngine (paper §4.1 analogue) against a bursty synthetic trace
and prints the reclaim/latency metrics the paper's Figs. 8–10 report.
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs.base import get_config, reduced
from repro.core.arena import ArenaSpec
from repro.models import model as M
from repro.serving.engine import ServeEngine
from repro.serving.request import PROFILES, Request
from repro.serving.tracegen import assign_profiles, bursty_trace


def serve(arch: str, *, mode: str = "hotmem", duration: float = 20.0,
          rate: float = 1.0, n_partitions: int = 8,
          partition_tokens: int = 128, keep_alive: float = 3.0,
          use_reduced: bool = True, seed: int = 0):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    spec = ArenaSpec.from_model(cfg, partition_tokens=partition_tokens,
                                n_partitions=n_partitions, block_tokens=32)
    arrivals = bursty_trace(duration, rate, burst_x=6.0, burst_at=(0.0,),
                            burst_len=duration / 6,
                            quiet_after=duration / 2, seed=seed)
    reqs = [Request(rid=f"r{i}", profile=p, submit_s=t)
            for i, (t, p) in enumerate(
                assign_profiles(arrivals, PROFILES, seed))]
    eng = ServeEngine(cfg, params, spec, mode=mode, keep_alive=keep_alive,
                      seed=seed)
    metrics = eng.run(reqs, max_virtual_s=duration * 40)
    return eng, metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="hotmem",
                    choices=["hotmem", "vanilla", "static"])
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--rate", type=float, default=1.0)
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--partition-tokens", type=int, default=128)
    ap.add_argument("--keep-alive", type=float, default=3.0)
    ap.add_argument("--reduced", action="store_true")
    a = ap.parse_args()
    enable_compile_cache()
    _, m = serve(a.arch, mode=a.mode, duration=a.duration, rate=a.rate,
                 n_partitions=a.partitions,
                 partition_tokens=a.partition_tokens,
                 keep_alive=a.keep_alive, use_reduced=a.reduced)
    m.pop("events")
    print(json.dumps(m, indent=2, default=str))


if __name__ == "__main__":
    main()
