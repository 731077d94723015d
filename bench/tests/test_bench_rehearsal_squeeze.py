"""CPU rehearsal of the squeeze mix through the whole harness at the
program's tiny widths: the metric arithmetic, and that every comparison
that decides ``correct`` runs.  The mix has no cell in BENCHMARK.json yet
(PERF.md, open questions); the rehearsal keeps it runnable."""
import json
import math

import pytest

from bench import run as R
from rehearsal import rehearse

CELL = "qwen2-1.5b.squeeze"


@pytest.fixture(scope="module")
def outcome():
    return rehearse(CELL, 2 ** 31 + 5)


def test_result_line(outcome):
    res, _lines = outcome
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s"}
    window = json.loads([ln for ln in _lines if ln.startswith(
        "window metrics: ")][0][len("window metrics: "):])
    assert set(window) >= {"ttft_p95_ms", "itl_p95_ms",
                           "reclaim_ms_per_gib", "output_tokens_per_s"}
    for v in window.values():
        assert v > 0 and math.isfinite(v)


def test_comparisons_ran(outcome):
    res, lines = outcome
    checks = res["checks"]
    assert set(checks) == {"broker_invariant_errors",
                           "restore_mismatch_elems", "mean_logit_gap"}
    assert checks["broker_invariant_errors"]["value"] == 0
    assert checks["restore_mismatch_elems"]["value"] == 0
    assert math.isfinite(checks["mean_logit_gap"]["value"])
    readings = [ln for ln in lines if ln.startswith("readings: ")]
    assert readings and '"served_tokens_compared"' in readings[0]


def test_p95_is_nearest_rank():
    assert R.p95(list(range(1, 101))) == 95
    assert R.p95([3.0]) == 3.0
    assert R.p95(list(range(1, 21))) == 19
