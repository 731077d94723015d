"""The comparison that decides ``correct`` catches a broken timed path.

Each case drives a whole CPU rehearsal of the saturate cell (the look for
a chip steered past) with the program's decode step broken underneath, and
sees ``correct`` come out false; the sound step comes out true.  The
exchange between chips has no case: the cell runs on one chip.

Keep-alive is the cell's, so most requests start warm, and the lengths
of the cell's first four functions are cut by 4 (prompts 64-128 tokens)
and 2 (decodes 16-128): a step that drops its state then changes what
later steps read, while the program's overwrite of slot 0 of every warm
row (ROADMAP R1) moves the logits far less than the limit.  Which requests
the sample holds depends on the wall clock; with four functions in it,
the sound step read about 1e-4 and a step that keeps its state 0.038 to
0.36 over twelve runs on a loaded CPU.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.models import model as M
from rehearsal import rehearse

CELL = "qwen2-1.5b.saturate"
STEP = M.decode_step


def state_unchanged(cfg, p, t, pos, c):
    """The step computes its logits but keeps the old cache."""
    return STEP(cfg, p, t, pos, c)[0], c


def half_batch(cfg, p, t, pos, c):
    """Half of the rows is left out: each odd row gets the logits of the
    even row before it."""
    logits, new = STEP(cfg, p, t, pos, c)
    return logits[jnp.arange(logits.shape[0]) // 2 * 2], new


def token_altered(cfg, p, t, pos, c):
    """The served token is altered where it is produced."""
    logits, new = STEP(cfg, p, t, pos, c)
    bump = jax.nn.one_hot(7, logits.shape[-1], dtype=logits.dtype) * 1e4
    return logits + bump, new


@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch,
                                   token_altered])
def test_fault_reads_incorrect(fault, monkeypatch):
    if fault is not None:
        monkeypatch.setattr(M, "decode_step", fault)
    res, lines = rehearse(CELL, 2 ** 31 + 9, seconds=1.0, prompt_div=4,
                          decode_div=2, n_functions=4)
    gap = res["checks"]["mean_logit_gap"]
    assert res["correct"] is (fault is None), (gap, lines)
