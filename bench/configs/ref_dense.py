"""Plain float32 reference of a dense GQA decoder (the Qwen2 family).

It follows the published Qwen2 block (arXiv:2407.10671): RMSNorm, q/k/v
projections with bias, rotary embeddings on the split halves of each head,
grouped-query causal softmax attention, an output projection without bias,
a SwiGLU MLP, residuals around both, a final RMSNorm and logits from the
tied embedding.  Everything is float32 at ``HIGHEST`` matmul precision over
the whole sequence at once: no cache, no kernels, no batching.  It imports
nothing of the program under test.

Beside it: the weights the benchmark makes from the seed (``make_weights``,
one jitted call on the device, bf16 as served), their arrangement into the
program's parameter tree (``to_program``), the program configuration
(``program_config``), and the shape arithmetic the metrics use
(``token_flops``, ``row_bytes``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
bf16 = jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                       # float8_e4m3fn


def dims(cj: dict) -> dict:
    d, hq = cj["hidden_size"], cj["num_attention_heads"]
    return {"L": cj["num_hidden_layers"], "d": d, "hq": hq,
            "hkv": cj["num_key_value_heads"], "dh": d // hq,
            "f": cj["intermediate_size"], "V": cj["vocab_size"]}


def reduced(cj: dict) -> dict:
    """The same family at the program's tiny test widths (CPU rehearsals)."""
    return {**cj, "num_hidden_layers": 2, "hidden_size": 64,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 128, "vocab_size": 512,
            "partition_tokens": 64, "n_partitions": 8}


# ---------------------------------------------------------------- weights
def _shapes(cj: dict) -> list:
    D = dims(cj)
    L, d, q, kv, f = D["L"], D["d"], D["hq"] * D["dh"], D["hkv"] * D["dh"], \
        D["f"]
    return [("embed", (D["V"], d), "embed"), ("ln1", (L, d), "norm"),
            ("wq", (L, d, q), "proj"), ("bq", (L, q), "bias"),
            ("wk", (L, d, kv), "proj"), ("bk", (L, kv), "bias"),
            ("wv", (L, d, kv), "proj"), ("bv", (L, kv), "bias"),
            ("wo", (L, q, d), "proj"), ("ln2", (L, d), "norm"),
            ("wg", (L, d, f), "proj"), ("wu", (L, d, f), "proj"),
            ("wd", (L, f, d), "proj"), ("norm", (d,), "norm")]


def make_weights(cj: dict, key) -> dict:
    """Weights from ``key``: bf16 matrices and biases, float32 norm
    scales (as the program stores them).  Jit it: it runs on the device."""
    w = cj["weights"]
    out = {}
    shapes = _shapes(cj)
    for (name, shape, kind), k in zip(shapes,
                                      jax.random.split(key, len(shapes))):
        z = jax.random.normal(k, shape, f32)
        if kind == "norm":
            out[name] = 1.0 + w["norm_std"] * z
        elif kind == "proj":
            out[name] = (z / math.sqrt(shape[-2])).astype(bf16)
        elif kind == "bias":
            out[name] = (w["bias_std"] * z).astype(bf16)
        else:
            out[name] = (w["embed_std"] * z).astype(bf16)
    return out


def program_config(cj: dict):
    """The program's ``ModelConfig`` at this file's sizes."""
    from repro.configs.base import get_config
    D = dims(cj)
    return dataclasses.replace(
        get_config(cj["program_arch"]), num_layers=D["L"], d_model=D["d"],
        num_heads=D["hq"], num_kv_heads=D["hkv"], head_dim=D["dh"],
        d_ff=D["f"], vocab_size=D["V"], rope_theta=cj["rope_theta"],
        norm_eps=cj["rms_norm_eps"], qkv_bias=True,
        tie_embeddings=cj["tie_word_embeddings"], sliding_window=0)


def to_program(cj: dict, w: dict, vocab_rows: int) -> dict:
    """Arrange the weights as the program's parameter tree (its embedding
    has ``vocab_rows`` rows; the padding rows are zero and never served)."""
    tok = jnp.pad(w["embed"], ((0, vocab_rows - w["embed"].shape[0]),
                               (0, 0)))
    layer = {"ln1": {"scale": w["ln1"]},
             "attn": {"q": {"w": w["wq"], "b": w["bq"]},
                      "k": {"w": w["wk"], "b": w["bk"]},
                      "v": {"w": w["wv"], "b": w["bv"]},
                      "o": {"w": w["wo"]}},
             "ln2": {"scale": w["ln2"]},
             "ffn": {"gate": {"w": w["wg"]}, "up": {"w": w["wu"]},
                     "down": {"w": w["wd"]}}}
    return {"embed": {"tok": tok},
            "decoder": {"groups": (layer,), "tail": (),
                        "final_norm": {"scale": w["norm"]}}}


# -------------------------------------------------------------- reference
def _q8(x):
    """Per-tensor scaled float8 (e4m3) rounding: the control's precision."""
    s = jnp.max(jnp.abs(x)) / FP8_MAX + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(f32) * s


def _mm(a, b, lowp: bool):
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=f32) / dh)
    ang = pos[:, None, None].astype(f32) * freqs           # (S, 1, dh/2)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def forward(cj: dict, w: dict, tokens, start, n_out: int,
            lowp: bool = False):
    """Logits (n_out, V) at positions [start, start + n_out) of the causal
    forward pass over ``tokens`` (S,).  ``lowp`` rounds every matmul's
    inputs to float8 (the control)."""
    D = dims(cj)
    hq, hkv, dh, eps = D["hq"], D["hkv"], D["dh"], cj["rms_norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        h = _rms(x, lw["ln1"], eps)
        q = (_mm(h, lw["wq"], lowp) + lw["bq"]).reshape(S, hq, dh)
        k = (_mm(h, lw["wk"], lowp) + lw["bk"]).reshape(S, hkv, dh)
        v = (_mm(h, lw["wv"], lowp) + lw["bv"]).reshape(S, hkv, dh)
        q, k = _rope(q, pos, cj["rope_theta"]), _rope(k, pos, cj["rope_theta"])
        k = jnp.repeat(k, hq // hkv, axis=1).transpose(1, 2, 0)   # (h,dh,S)
        v = jnp.repeat(v, hq // hkv, axis=1).transpose(1, 0, 2)   # (h,S,dh)
        s = _mm(q.transpose(1, 0, 2), k, lowp) / math.sqrt(dh)    # (h,S,S)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = _mm(p, v, lowp).transpose(1, 0, 2).reshape(S, hq * dh)
        x = x + _mm(o, lw["wo"], lowp)
        h = _rms(x, lw["ln2"], eps)
        m = jax.nn.silu(_mm(h, lw["wg"], lowp)) * _mm(h, lw["wu"], lowp)
        return x + _mm(m, lw["wd"], lowp), None

    per_layer = {k: w[k] for k in ("ln1", "wq", "bq", "wk", "bk", "wv", "bv",
                                   "wo", "ln2", "wg", "wu", "wd")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    x = _rms(jax.lax.dynamic_slice_in_dim(x, start, n_out), w["norm"], eps)
    return _mm(x, w["embed"].astype(f32).T, lowp)


# ------------------------------------------------------- shape arithmetic
def token_flops(cj: dict, context: int) -> float:
    """Model FLOPs of one token attending ``context`` positions, LM head
    excluded: two per weight of every block matmul, plus QK^T and AV."""
    D = dims(cj)
    q, kv = D["hq"] * D["dh"], D["hkv"] * D["dh"]
    per_layer = D["d"] * (2 * q + 2 * kv) + 3 * D["d"] * D["f"]
    return 2.0 * D["L"] * per_layer + 4.0 * D["L"] * q * context


def head_flops(cj: dict) -> float:
    D = dims(cj)
    return 2.0 * D["d"] * D["V"]


def row_bytes(cj: dict, tokens: int) -> int:
    """Bytes of one request's bf16 KV at ``tokens`` positions."""
    D = dims(cj)
    return D["L"] * 2 * tokens * D["hkv"] * D["dh"] * 2
