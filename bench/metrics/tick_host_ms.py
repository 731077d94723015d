"""Mean host self time of one scheduler tick around its decode step, in
ms: over the window's decode events, the sum of the phases of the tick that
ran the step (``StepEvent.phases``: grant claims, order drains, admission,
the step's inputs, per-row bookkeeping, recycling, resizing) other than the
step's own ``launch`` and ``wait``.  Device work nested in a phase (a
prefill, a snapshot, a resize copy) is not part of its self time."""

STEP = ("launch", "wait")


def read(view):
    got = [sum(v for k, v in ph.items() if k not in STEP)
           for _rid, ev in view.events
           if ev.kind == "decode"
           and (ph := getattr(ev, "phases", None)) is not None]
    return 1e3 * sum(got) / len(got) if got else None
