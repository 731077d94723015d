"""95th percentile of the gaps between successive output tokens of every
request in the window, by the host clock (nearest rank), in ms."""
import math


def read(view):
    gaps = sorted(view.win.itl)
    if not gaps:
        return None
    return 1e3 * gaps[max(0, math.ceil(0.95 * len(gaps)) - 1)]
