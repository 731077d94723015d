"""Plain float32 reference of a Mamba-2 language model (SSD layers only).

It follows the published Mamba-2 block (arXiv:2405.21060, ``Mamba2`` with
its defaults: one group, no ``D`` per channel, gated RMSNorm after the
gate): RMSNorm, the input projections z / x / B / C / dt, a causal
depthwise convolution of width ``d_conv`` with SiLU on x, B and C,
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the diagonal state
recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``,
``y_t = C_t h_t + D x_t``, ``RMSNorm(y * silu(z))``, the output projection
and a residual; then a final RMSNorm and logits from the tied embedding.
The recurrence runs token by token (``lax.scan``) in float32 at
``HIGHEST`` matmul precision: no chunking, no cache, no kernels.  It
imports nothing of the program under test.

Departure, noted: the program keeps the residual stream in bf16, while the
published model keeps it in float32 (``residual_in_fp32``); the reference
is float32 throughout.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
bf16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def dims(cj: dict) -> dict:
    m = cj["mamba2_defaults"]
    d = cj["d_model"]
    di = m["expand"] * d
    mult = cj["pad_vocab_size_multiple"]
    return {"L": cj["n_layer"], "d": d, "di": di, "P": m["headdim"],
            "H": di // m["headdim"], "N": m["d_state"], "G": m["ngroups"],
            "W": m["d_conv"], "V": cj["vocab_size"],
            "Vp": -(-cj["vocab_size"] // mult) * mult}


def reduced(cj: dict) -> dict:
    m = dict(cj["mamba2_defaults"], d_state=16, headdim=16, chunk_size=8)
    return {**cj, "n_layer": 2, "d_model": 64, "vocab_size": 512,
            "mamba2_defaults": m, "partition_tokens": 64, "n_partitions": 8}


def _shapes(cj: dict) -> list:
    D = dims(cj)
    L, d, di, H, N, G, W = (D[k] for k in "L d di H N G W".split())
    return [("embed", (D["Vp"], d), "embed"), ("ln", (L, d), "norm"),
            ("wz", (L, d, di), "proj"), ("wx", (L, d, di), "proj"),
            ("wB", (L, d, G * N), "proj"), ("wC", (L, d, G * N), "proj"),
            ("wdt", (L, d, H), "proj"),
            ("cx", (L, W, di), "conv"), ("cxb", (L, di), "bias"),
            ("cB", (L, W, G * N), "conv"), ("cBb", (L, G * N), "bias"),
            ("cC", (L, W, G * N), "conv"), ("cCb", (L, G * N), "bias"),
            ("A_log", (L, H), "a_log"), ("Dskip", (L, H), "one"),
            ("dt_bias", (L, H), "dt_bias"), ("gnorm", (L, di), "norm"),
            ("wo", (L, di, d), "proj"), ("norm", (d,), "norm")]


def make_weights(cj: dict, key) -> dict:
    """bf16 projections, convolutions and embedding; float32 norms, A_log,
    D and dt_bias (as the program stores them)."""
    w = cj["weights"]
    m = cj["mamba2_defaults"]
    out = {}
    shapes = _shapes(cj)
    for (name, shape, kind), k in zip(shapes,
                                      jax.random.split(key, len(shapes))):
        z = jax.random.normal(k, shape, f32)
        if kind == "norm":
            out[name] = 1.0 + w["norm_std"] * z
        elif kind == "proj":
            out[name] = (z / math.sqrt(shape[-2])).astype(bf16)
        elif kind == "conv":
            out[name] = (w["conv_std"] * z).astype(bf16)
        elif kind == "bias":
            out[name] = (w["bias_std"] * z).astype(bf16)
        elif kind == "a_log":
            u = jax.random.uniform(k, shape, f32, *m["A_init_range"])
            out[name] = jnp.log(u)
        elif kind == "dt_bias":
            lo, hi = math.log(m["dt_min"]), math.log(m["dt_max"])
            dt = jnp.exp(jax.random.uniform(k, shape, f32) * (hi - lo) + lo)
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        elif kind == "one":
            out[name] = jnp.ones(shape, f32)
        else:
            out[name] = (w["embed_std"] * z).astype(bf16)
    return out


def program_config(cj: dict):
    from repro.configs.base import get_config
    D = dims(cj)
    m = cj["mamba2_defaults"]
    return dataclasses.replace(
        get_config(cj["program_arch"]), num_layers=D["L"], d_model=D["d"],
        vocab_size=D["Vp"], ssm_state=D["N"], ssm_headdim=D["P"],
        ssm_expand=m["expand"], ssm_ngroups=D["G"], ssm_conv_width=D["W"],
        ssm_chunk=m["chunk_size"], norm_eps=cj["norm_eps"],
        tie_embeddings=cj["tie_embeddings"])


def to_program(cj: dict, w: dict, vocab_rows: int) -> dict:
    tok = jnp.pad(w["embed"], ((0, vocab_rows - w["embed"].shape[0]),
                               (0, 0)))
    mixer = {"z": {"w": w["wz"]}, "x": {"w": w["wx"]},
             "B": {"w": w["wB"]}, "C": {"w": w["wC"]},
             "dt": {"w": w["wdt"]},
             "conv_x": {"w": w["cx"], "b": w["cxb"]},
             "conv_B": {"w": w["cB"], "b": w["cBb"]},
             "conv_C": {"w": w["cC"], "b": w["cCb"]},
             "A_log": w["A_log"], "D": w["Dskip"], "dt_bias": w["dt_bias"],
             "norm": {"scale": w["gnorm"]}, "out": {"w": w["wo"]}}
    layer = {"ln": {"scale": w["ln"]}, "mixer": mixer}
    return {"embed": {"tok": tok},
            "decoder": {"groups": (layer,), "tail": (),
                        "final_norm": {"scale": w["norm"]}}}


def _q8(x):
    s = jnp.max(jnp.abs(x)) / FP8_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s


def _mm(a, b, lowp: bool):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _conv(x, w, b):
    """Causal depthwise convolution over time; x (S, C), w (W, C)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    y = sum(xp[i:i + x.shape[0]] * w[i] for i in range(W))
    return jax.nn.silu(y + b)


def forward(cj: dict, w: dict, tokens, start, n_out: int,
            lowp: bool = False):
    D = dims(cj)
    S, H, P, N, G = tokens.shape[0], D["H"], D["P"], D["N"], D["G"]
    eps = cj["norm_eps"]
    x = w["embed"][tokens].astype(f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        u = _rms(x, lw["ln"], eps)
        z = _mm(u, lw["wz"], lowp)
        xs = _conv(_mm(u, lw["wx"], lowp), lw["cx"], lw["cxb"])
        Bs = _conv(_mm(u, lw["wB"], lowp), lw["cB"], lw["cBb"])
        Cs = _conv(_mm(u, lw["wC"], lowp), lw["cC"], lw["cCb"])
        dt = jax.nn.softplus(_mm(u, lw["wdt"], lowp) + lw["dt_bias"])
        A = -jnp.exp(lw["A_log"])
        xh = xs.reshape(S, H, P)
        Bh = jnp.repeat(Bs.reshape(S, G, N), H // G, axis=1)
        Ch = jnp.repeat(Cs.reshape(S, G, N), H // G, axis=1)

        def step(h, inp):
            xt, Bt, Ct, dtt = inp
            h = h * jnp.exp(dtt * A)[:, None, None] \
                + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, Ct, precision=HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32),
                            (xh, Bh, Ch, dt))
        y = (y + xh * lw["Dskip"][:, None]).reshape(S, H * P)
        y = _rms(y * jax.nn.silu(z), lw["gnorm"], eps)
        return x + _mm(y, lw["wo"], lowp), None

    per_layer = {k: w[k] for k in ("ln", "wz", "wx", "wB", "wC", "wdt",
                                   "cx", "cxb", "cB", "cBb", "cC", "cCb",
                                   "A_log", "Dskip", "dt_bias", "gnorm",
                                   "wo")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _rms(jax.lax.dynamic_slice_in_dim(x, start, n_out), w["norm"], eps)
    return _mm(x, w["embed"].astype(f32).T, lowp)


def token_flops(cj: dict, context: int) -> float:
    """Model FLOPs of one token, LM head excluded: two per weight of every
    projection, plus the state update and readout (constant in context)."""
    D = dims(cj)
    d, di, H, P, N, G = (D[k] for k in "d di H P N G".split())
    proj = d * (2 * di + 2 * G * N + H) + di * d
    ssd = 4 * H * P * N
    return 2.0 * D["L"] * proj + D["L"] * ssd


def head_flops(cj: dict) -> float:
    D = dims(cj)
    return 2.0 * D["d"] * D["Vp"]


def row_bytes(cj: dict, tokens: int) -> int:
    """Bytes of one request's bf16 state: SSM state plus conv histories,
    whatever the context."""
    D = dims(cj)
    W = D["W"] - 1
    per = D["H"] * D["P"] * D["N"] + W * (D["di"] + 2 * D["G"] * D["N"])
    return D["L"] * per * 2
