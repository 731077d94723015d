"""Cold-start prefill wall per thousand prompt tokens: the engine's
``prefill`` step spans (a ``perf_counter`` wall around work that ends in
``block_until_ready``) in the window, over the prompt tokens they ran."""


def read(view):
    ms, tok = 0.0, 0
    for _rid, ev in view.events:
        if ev.kind == "prefill" and ev.detail["rid"] in view.prompt_of:
            ms += 1e3 * ev.wall_s
            tok += view.prompt_of[ev.detail["rid"]]
    return ms / (tok / 1e3) if tok else None
