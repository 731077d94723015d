"""Share of the traced window in which no executable ran on the chip, in
percent (1 - union of device module intervals / window)."""


def read(view):
    t = view.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
