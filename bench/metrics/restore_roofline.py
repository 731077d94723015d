"""Snapshot restore's share of its HBM roofline, in percent: for each
restore executable traced in the window, the least time the chip could
take (read the staged blob and write the row: 2 x row bytes at peak HBM
bandwidth) over the executable's device time.  The whole executable is
measured, whatever implements it inside."""

NAME = "kv_snapshot_restore"


def read(view):
    return roofline(view, NAME)


def roofline(view, name):
    t = view.trace
    if t is None or view.peak is None:
        return None
    hits = [v for k, v in t.executables.items() if name in k]
    launches = sum(n for n, _s in hits)
    secs = sum(s for _n, s in hits)
    if not launches or secs <= 0:
        return None
    ideal = launches * 2 * view.row_bytes / view.peak["hbm_bytes_per_s"]
    return 100.0 * ideal / secs
