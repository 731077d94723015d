"""Mixture-of-Experts FFN: top-k routing with expert capacity.

Tokens are scattered into a dense (E, C, D) dispatch buffer (C = per-expert
capacity), batched-matmul'd through the stacked expert weights, and gathered
back with combine weights.  HLO FLOPs are therefore proportional to
*active* experts (E*C ~ top_k * tokens * capacity_factor), matching the
MoE roofline's 6*N_active*D accounting.

Sharding: expert dim -> "model" (EP, dbrx 16e) or expert d_ff -> "model"
(TP, mixtral 8e, since 8 does not divide the 16-way axis); capacity dim ->
("pod","data") so dispatch buffers stay per-chip-sized.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, act_fn, f32
from repro.sharding import shard


def moe_spec(cfg):
    e, d, ff = cfg.num_experts, cfg.d_model, cfg.d_ff
    wa = ("experts", "w_embed", "expert_mlp")
    return {
        "router": ParamSpec((d, e), f32, (None, None)),   # tiny: replicated
        "gate": ParamSpec((e, d, ff), axes=wa),
        "up": ParamSpec((e, d, ff), axes=wa),
        "down": ParamSpec((e, ff, d), axes=("experts", "expert_mlp",
                                            "w_embed")),
    }


def capacity(cfg, n_tokens: int) -> int:
    c = int(n_tokens * cfg.num_experts_per_tok * cfg.moe_capacity_factor
            / cfg.num_experts)
    return max(((c + 7) // 8) * 8, 8)


def moe_block(cfg, p, x):
    """x: (B, S, D) -> (B, S, D).  Dispatches to the shard_map path when a
    mesh context is active (GSPMD cannot partition the capacity scatter —
    it replicates multi-GiB dispatch buffers per chip and floods ICI with
    full-buffer all-reduces; the shard_map path keeps dispatch device-local
    and pays exactly one psum per layer, like a dense TP MLP)."""
    from repro.sharding import current_ctx
    ctx = current_ctx()
    if ctx is not None and "model" in ctx.mesh.shape:
        return _moe_shard_map(cfg, p, x, ctx)
    return _moe_dense(cfg, p, x)


def _moe_shard_map(cfg, p, x, ctx):
    from repro.sharding import spec_for, shard
    from jax.sharding import PartitionSpec as P

    e, k = cfg.num_experts, cfg.num_experts_per_tok
    ep = cfg.moe_sharding == "ep"
    x = shard(x, "batch", "seq", None)          # tokens: DP only
    xs = spec_for(("batch", "seq", None), x.shape, ctx)
    gs = spec_for(("experts", "w_embed", "expert_mlp"), p["gate"].shape, ctx)
    ds_ = spec_for(("experts", "expert_mlp", "w_embed"), p["down"].shape,
                   ctx)
    model_size = ctx.mesh.shape.get("model", 1)

    def gather_dim(w, spec, dim):
        ax = spec[dim] if dim < len(spec) else None
        if ax is None:
            return w
        return jax.lax.all_gather(w, ax, axis=dim, tiled=True)

    CHUNK = 16384        # bound dispatch-buffer size at long prefills

    def tokens_fn(xt, router, gate, up, down):
        """One chunk of local tokens through the local experts."""
        n, dm = xt.shape
        logits = xt.astype(f32) @ router                       # (n, E)
        top_w, top_e = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        flat_e = top_e.reshape(-1)
        flat_w = top_w.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        c = max(((int(n * k * cfg.moe_capacity_factor / e) + 7) // 8) * 8, 8)
        keep = rank < c
        rank = jnp.where(keep, rank, 0)
        src = jnp.repeat(jnp.arange(n), k)

        if ep:      # scatter straight into the LOCAL experts' buffer only
            e_loc = gate.shape[0]
            e0 = jax.lax.axis_index("model") * e_loc
            local_expert = (flat_e >= e0) & (flat_e < e0 + e_loc)
            le = jnp.where(local_expert, flat_e - e0, e_loc)   # OOB -> drop
            buf = jnp.zeros((e_loc, c, dm), xt.dtype)
            buf = buf.at[le, rank].add(
                xt[src] * keep[:, None].astype(xt.dtype), mode="drop")
            le = jnp.where(local_expert, flat_e - e0, 0)
        else:       # TP: all experts locally, F sliced
            local_expert = None
            le = flat_e
            buf = jnp.zeros((e, c, dm), xt.dtype)
            buf = buf.at[flat_e, rank].add(
                xt[src] * keep[:, None].astype(xt.dtype), mode="drop")

        h = act_fn(cfg.act, jnp.einsum("ecd,edf->ecf", buf, gate)) \
            * jnp.einsum("ecd,edf->ecf", buf, up)
        out = jnp.einsum("ecf,efd->ecd", h, down)              # partial in D

        gathered = out[le, rank]                               # (n*K, D)
        w = flat_w * keep
        if local_expert is not None:
            w = w * local_expert
        gathered = gathered * w[:, None].astype(xt.dtype)
        return jnp.zeros((n, dm), xt.dtype).at[src].add(gathered)

    def local_fn(xb, router, gate, up, down):
        bl, sl, dm = xb.shape
        n = bl * sl
        xt = xb.reshape(n, dm)
        # FSDP'd weight dims are gathered explicitly (the all-gather XLA
        # would insert outside shard_map, now visible and overlappable)
        gate = gather_dim(gate, gs, 1)
        up = gather_dim(up, gs, 1)
        down = gather_dim(down, ds_, 2)

        if n <= CHUNK:
            y = tokens_fn(xt, router, gate, up, down)
        else:
            nc = -(-n // CHUNK)
            pad = nc * CHUNK - n
            xp = jnp.pad(xt, ((0, pad), (0, 0))).reshape(nc, CHUNK, dm)
            y = jax.lax.map(
                lambda ch: tokens_fn(ch, router, gate, up, down), xp)
            y = y.reshape(nc * CHUNK, dm)[:n]
        y = jax.lax.psum(y, "model")      # combine experts (EP) / F (TP)
        return y.reshape(bl, sl, dm)

    fn = jax.shard_map(local_fn, mesh=ctx.mesh,
                       in_specs=(xs, P(None, None), gs, gs, ds_),
                       out_specs=xs, check_vma=False)
    return fn(x, p["router"], p["gate"], p["up"], p["down"])


def _moe_dense(cfg, p, x):
    """Reference path (no mesh): capacity-based scatter/gather."""
    b, s, d = x.shape
    k, e = cfg.num_experts_per_tok, cfg.num_experts
    n = b * s
    c = capacity(cfg, n)
    xt = x.reshape(n, d)

    logits = (xt.astype(f32) @ p["router"])                     # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                      # (N, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)                                  # (N*K,)
    flat_w = top_w.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)         # (N*K, E)
    rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1    # (N*K,)
    keep = rank < c                                             # drop overflow
    rank = jnp.where(keep, rank, 0)
    src = jnp.repeat(jnp.arange(n), k)                          # token per slot

    buf = jnp.zeros((e, c, d), x.dtype)
    buf = buf.at[flat_e, rank].add(
        xt[src] * keep[:, None].astype(x.dtype), mode="drop")
    buf = shard(buf, "experts", "expert_cap", None)

    h = act_fn(cfg.act, jnp.einsum("ecd,edf->ecf", buf, p["gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["up"])
    h = shard(h, "experts", "expert_cap", "expert_mlp")
    out = jnp.einsum("ecf,efd->ecd", h, p["down"])
    out = shard(out, "experts", "expert_cap", None)

    gathered = out[flat_e, rank]                                # (N*K, D)
    gathered = gathered * (flat_w * keep)[:, None].astype(x.dtype)
    y = jnp.zeros((n, d), x.dtype).at[src].add(gathered)
    return y.reshape(b, s, d)
