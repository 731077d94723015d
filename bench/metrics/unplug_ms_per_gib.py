"""Arena shrink wall per GiB given back: the engine's ``unplug`` step spans
in the window (manager metadata, the row slice and its sync), over the
bytes they reclaimed."""


def read(view):
    ms, got = 0.0, 0
    for _rid, ev in view.events:
        if ev.kind == "unplug" and ev.detail.get("reclaimed_bytes"):
            ms += 1e3 * ev.wall_s
            got += ev.detail["reclaimed_bytes"]
    return ms / (got / 2 ** 30) if got else None
