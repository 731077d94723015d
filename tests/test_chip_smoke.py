"""``chip_smoke.py`` off the chip: its phases at a tiny size on CPU, and
its refusal to report a result without a TPU."""
import importlib.util
import os

import pytest

from repro.configs.base import get_config, reduced


@pytest.fixture(scope="module")
def chip_smoke():
    path = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_at_reduced_width(chip_smoke):
    """Every phase the chip run asserts also holds on a reduced qwen2-1.5b:
    cold / warm / restore starts, an order drained by B, Pallas (here
    interpreted) capture == ref, the restore round trip, decode vs the
    full forward."""
    obs = chip_smoke.run_smoke(reduced(get_config("qwen2-1.5b")),
                               partition_tokens=128)
    assert obs["completed"] == len(chip_smoke._requests())
    assert obs["order_drains"] >= 1
    assert obs["max_rows"]["A"] > 16   # A's burst outgrew half the arena


def test_smoke_refuses_without_a_tpu(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "needs a TPU" in err
