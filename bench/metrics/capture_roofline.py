"""Snapshot capture's share of its HBM roofline, in percent: for each
capture executable traced in the window, 2 x row bytes (read the row,
write the staging blob) at peak HBM bandwidth, over the executable's
device time.  The device-to-host copy of the blob is not part of it."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_restore_roofline",
    Path(__file__).with_name("restore_roofline.py"))
_restore = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_restore)


def read(view):
    return _restore.roofline(view, "kv_snapshot_capture")
