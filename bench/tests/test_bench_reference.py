"""Each configuration's plain reference agrees with the program's own
full forward pass on the weights the benchmark makes (arranged by the
reference's ``to_program``), at the program's tiny test widths."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from repro.models import model as M


@pytest.mark.parametrize("config,ref_name", [("qwen2-1.5b", "ref_dense"),
                                             ("mamba2-780m", "ref_ssm")])
def test_reference_matches_program_forward(config, ref_name):
    ref = R.load_module(R.BENCH / "configs" / f"{ref_name}.py", ref_name)
    cj = ref.reduced(json.loads(
        (R.BENCH / "configs" / f"{config}.json").read_text()))
    cfg = ref.program_config(cj)
    rows = M.param_specs(cfg)["embed"]["tok"].shape[0]
    w = ref.make_weights(cj, jax.random.key(3))
    params = ref.to_program(cj, w, rows)
    toks = np.zeros(24, np.int32)
    toks[:16] = 11
    want = M.forward_train(cfg, params, {"tokens": jnp.asarray(toks)[None]},
                           remat=False)[0].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ref.forward(cj, w, jnp.asarray(toks), 0, 24)
    scale = float(jnp.max(jnp.abs(got)))
    # the program runs in bf16: agreement to a few bf16 roundings
    assert float(jnp.max(jnp.abs(got - want[:, :got.shape[1]]))) \
        < 0.05 * scale
