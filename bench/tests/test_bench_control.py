"""The control fails the limit: the float32 reference with every matmul's
inputs rounded to float8 (e4m3, per-tensor scale), read at each position
of a saturate request's decode, picks tokens whose reference logit lies
below the best by more, on the mean over the positions, than the cell's
limit allows.

At qwen2-1.5b's published widths with four of its 28 layers (what a CPU
test holds); the chip readings at all 28 layers, through the harness's
``--control 1``, are in PERF.md."""
import json

import jax
import jax.numpy as jnp
import numpy as np
from bench import run as R
from rehearsal import rehearse

CELL = "qwen2-1.5b.saturate"


def test_float8_control_fails_the_limit(seed=2):
    limit = R.limits_for(CELL)["mean_logit_gap"]
    cj = json.loads((R.BENCH / "configs" / "qwen2-1.5b.json").read_text())
    cj["num_hidden_layers"] = 4
    ref = R.load_module(R.BENCH / "configs" / "ref_dense.py", "ref_dense")
    w = jax.jit(lambda k: ref.make_weights(cj, k))(jax.random.key(seed))
    n, d = 256, 64                       # a saturate function's lengths
    toks = np.zeros(n + d, np.int32)
    toks[:n] = 17 + seed
    with jax.default_matmul_precision("highest"):
        lg = jax.jit(lambda w, x: ref.forward(cj, w, x, n, d))(
            w, jnp.asarray(toks))
        lo = jax.jit(lambda w, x: ref.forward(cj, w, x, n, d, lowp=True))(
            w, jnp.asarray(toks))
    pick = jnp.argmax(lo, -1)
    gap = lg.max(-1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
    assert float(gap.mean()) > limit


def test_control_is_judged_in_the_programs_place(monkeypatch):
    """With ``control`` the harness's ``mean_logit_gap`` is the float8
    reference's reading, and ``correct`` follows it; the program's own
    reading is printed beside it."""
    seen = {}

    def spy(*args, **kw):
        seen.update(compare(*args, **kw))
        return seen

    compare = R.compare
    monkeypatch.setattr(R, "compare", spy)
    res, lines = rehearse(CELL, 5, seconds=1.0, prompt_div=4, decode_div=2,
                          control=True)
    read = json.loads([ln for ln in lines if ln.startswith(
        "readings: ")][0][len("readings: "):])
    gap = res["checks"]["mean_logit_gap"]
    assert gap["value"] == seen["control_mean"]
    assert read["program_mean_logit_gap"] == seen["mean"]
    assert res["correct"] is (gap["value"] <= gap["limit"])
