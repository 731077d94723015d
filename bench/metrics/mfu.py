"""Whole-window model FLOP utilisation, in percent: the FLOPs of every
prompt token prefilled and every live output token decoded in the window
(two per weight of each block matmul and of the LM head, plus attention
over each request's own context; ``RunView.step_flops``), over the
window's seconds, over the chip's bf16 peak.  Rows decoded for no request
count for nothing."""


def read(view):
    if view.peak is None:
        return None
    flops = view.step_flops()
    if not flops:
        return None
    return 100.0 * flops / view.seconds / view.peak["bf16_flops_per_s"]
