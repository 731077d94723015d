"""CPU rehearsal of the saturate mix: one replica, no pool, no orders."""
import math

import pytest

from bench import run as R
from rehearsal import rehearse

CELL = "qwen2-1.5b.saturate"


@pytest.fixture(scope="module")
def outcome():
    return rehearse(CELL, 3)


def test_result_line(outcome):
    res, lines = outcome
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] > 0
    for v in res["metrics"].values():
        assert v["value"] > 0 and math.isfinite(v["value"])
    window = [ln for ln in lines if ln.startswith("window metrics: ")][0]
    assert "reclaim_ms_per_gib" not in window       # no order was issued
    assert res["checks"]["restore_mismatch_elems"]["value"] == 0


def test_off_a_tpu_exits_nonzero(capsys):
    assert R.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err
