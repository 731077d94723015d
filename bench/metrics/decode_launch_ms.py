"""Mean host time to launch one decode step, in ms: the ``launch`` phase
of the window's decode events (``StepEvent.phases``), from the start of the
step's wall to the return of the jitted call.  It covers the two input
transfers, dispatch, donation and whatever wraps the step's call, before
the device has the step."""


def read(view):
    got = [ph["launch"] for _rid, ev in view.events
           if ev.kind == "decode"
           and (ph := getattr(ev, "phases", None)) is not None]
    return 1e3 * sum(got) / len(got) if got else None
