"""Mean device time of one decode step, in ms: the seconds of every
executable whose name contains ``decode_step`` (the engine's named decode
jit, ``jit_decode_step`` at every arena size) traced in the window, over
its launches there."""

NAME = "decode_step"


def read(view):
    t = view.trace
    if t is None:
        return None
    hits = [v for k, v in t.executables.items() if NAME in k]
    launches = sum(n for n, _s in hits)
    secs = sum(s for _n, s in hits)
    return 1e3 * secs / launches if launches and secs > 0 else None
