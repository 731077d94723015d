"""Shared set-up of the CPU rehearsals: a cell at the program's tiny test
widths, with its mix's lengths scaled down, served through the whole
harness except the look for a chip."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import loadgen  # noqa: E402
from bench import run as R  # noqa: E402


def with_cell(bench: dict, workload: str) -> dict:
    """The benchmark with ``<config>.<mix>`` as a cell, whether or not
    ``BENCHMARK.json`` lists it (a mix kept for a later cell is still
    rehearsed)."""
    if any(w["name"] == workload for w in bench["workloads"]):
        return bench
    config, mix = workload.rsplit(".", 1)
    return {**bench, "workloads": bench["workloads"] + [
        {"name": workload, "config": config, "traffic": mix, "chips": 1}]}


def reduced_cell(workload: str, decode_div: int = 16, prompt_div: int = 4,
                 n_functions: int = 2, **mix_overrides):
    bench = with_cell(R.load_benchmark(), workload)
    cell, conf = R.find_cell(bench, workload)
    cj = json.loads((R.ROOT / conf["file"]).read_text())
    ref = R.load_module(R.BENCH / "configs" / f"{cj['reference']}.py",
                        f"bench_ref_{cj['reference']}")
    cj = ref.reduced(cj)
    cj["partition_tokens"] = max(cj["partition_tokens"],
                                 1536 // prompt_div + 256 // decode_div)
    cj["n_partitions"] = 4
    mix = loadgen.load_mix(cell["traffic"])
    for t in mix["tenants"]:
        t["functions"] = t["functions"][:n_functions]
        for f in t["functions"]:
            f["prompt"] //= prompt_div
            f["decode"] //= decode_div
    # pressure at four partitions a replica: with a pool, the budget is
    # one partition short of every arena at its top rung
    n_rep, pool = len(mix["replicas"]), min(1, mix["pool_partitions"])
    budget = n_rep * 4 - pool
    mix.update(warmup_s=0.3, grace_s=10.0, budget_partitions=budget,
               pool_partitions=pool)
    if mix["arrivals"]["kind"] == "closed":     # outnumber the four rows
        mix["arrivals"] = {**mix["arrivals"], "clients": 6}
    else:
        mix["rate_per_s"] *= 2
    mix.update(mix_overrides)
    return bench, cj, mix


def rehearse(workload: str, seed: int, seconds: float = 1.0, **kw):
    overrides = kw.pop("mix", {})
    bench, cj, mix = reduced_cell(workload, kw.pop("decode_div", 16),
                                  kw.pop("prompt_div", 4),
                                  kw.pop("n_functions", 2), **overrides)
    return R.run_cell(workload, seed, seconds, False, bench=bench,
                      cj_override=cj, mix_override=mix, **kw)
