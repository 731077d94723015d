"""Mean wall of one decode step of one replica (the engine's ``decode``
step spans in the window)."""


def read(view):
    walls = [ev.wall_s for _rid, ev in view.events if ev.kind == "decode"]
    return 1e3 * sum(walls) / len(walls) if walls else None
