"""Mixture-of-experts family (olmoe-1b-7b, qwen2-moe-a2.7b).

Dispatch is capacity-bounded scatter/gather ("dropping" MoE): token->slot
ranks come from a cumsum over the routing one-hot, tokens are scattered into a
per-expert (E, C, D) buffer that is expert-sharded on the model axis, expert
FFNs run as one batched einsum, and outputs are gathered back and combined
with the gates. XLA inserts the data->expert all-to-alls from the sharding
constraints.

Experts whose published count does not divide the mesh (qwen2-moe: 60) are
padded to the next multiple of 16 with router-logit masking (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import pad_to
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.params import Spec, prefix, subtree


def padded_experts(cfg) -> int:
    return pad_to(cfg.num_experts, 16) if cfg.num_experts > 16 else cfg.num_experts


def capacity(cfg, tokens: int) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.num_experts)
    return max(pad_to(c, 8), 8)


def moe_specs(cfg, stack=()) -> dict[str, Spec]:
    st = tuple("layers" for _ in stack)
    D, F, Ep = cfg.d_model, cfg.moe_d_ff, padded_experts(cfg)
    sp = {
        # router is tiny — replicate it so the shard_map EP dispatch can read
        # it without a gather
        "router": Spec(stack + (D, Ep), st + (None, None)),
        "wg": Spec(stack + (Ep, D, F), st + ("experts", "embed", "ff")),
        "wu": Spec(stack + (Ep, D, F), st + ("experts", "embed", "ff")),
        "wd": Spec(stack + (Ep, F, D), st + ("experts", "ff", "embed")),
    }
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        sp["shared_wg"] = Spec(stack + (D, Fs), st + ("embed", "ff"))
        sp["shared_wu"] = Spec(stack + (D, Fs), st + ("embed", "ff"))
        sp["shared_wd"] = Spec(stack + (Fs, D), st + ("ff", "embed"))
        # qwen2-moe gates the shared expert with a sigmoid over a linear probe
        sp["shared_gate"] = Spec(stack + (D, 1), st + ("embed", None), "zeros")
    return sp


def _local_dispatch(xf, logits, cfg, E, Ep, C, dtype):
    """Capacity-bounded scatter dispatch over LOCAL tokens (no comms)."""
    k = cfg.top_k
    T = xf.shape[0]
    if Ep > E:
        logits = jnp.where(jnp.arange(Ep) >= E, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    ohk = jax.nn.one_hot(idx, Ep, dtype=jnp.float32)
    f_e = ohk.sum(1).mean(0)  # per-expert routed fraction (local moments)
    p_e = probs.mean(0)
    aux = (f_e, p_e)

    flat_e = idx.reshape(-1)
    oh = jax.nn.one_hot(flat_e, Ep, dtype=jnp.int32)
    slot = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1
    keep = slot < C
    slot = jnp.where(keep, slot, 0)
    tok_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    xg = jnp.take(xf, tok_idx, axis=0) * keep[:, None].astype(dtype)
    buf = jnp.zeros((Ep, C, xf.shape[-1]), dtype).at[flat_e, slot].add(xg, mode="drop")
    return buf, (flat_e, slot, keep, gates, tok_idx, T), aux


def _local_combine(out_buf, meta, dtype, D):
    flat_e, slot, keep, gates, tok_idx, T = meta
    yk = out_buf[flat_e, slot] * (gates.reshape(-1)[:, None] * keep[:, None]).astype(dtype)
    return jnp.zeros((T, D), dtype).at[tok_idx].add(yk, mode="drop")


def moe_ffn_ep(p, x, cfg, mesh):
    """Expert-parallel dispatch under shard_map (§Perf cell B).

    Tokens stay on their (data, seq) shard; per-chip local top-k + capacity
    scatter builds an (Ep, C_loc, D) buffer; a TILED all-to-all over the
    model axis exchanges expert slices (each chip keeps only its Ep/16
    experts at 16x the local capacity); expert FFNs run as one batched
    einsum; the reverse all-to-all returns outputs for local combine. The
    pjit scatter fallback lowers to DENSE fp32 all-reduces of token-sized
    buffers — this path replaces them with two a2a's of the dispatched
    tokens only.
    """
    from jax.sharding import PartitionSpec as P

    Bb, S, D = x.shape
    E, Ep = cfg.num_experts, padded_experts(cfg)
    msize = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dshards = 1
    for a in batch_axes:
        dshards *= mesh.shape[a]
    t_loc = (Bb // dshards) * (S // msize)
    C_loc = max(int(t_loc * cfg.top_k * cfg.capacity_factor / E), 8)

    def shard_fn(xl, router, wg, wu, wd):
        # xl: (B_loc, S_loc, D) — flatten local tokens
        b_l, s_l, _ = xl.shape
        xf = xl.reshape(b_l * s_l, D)
        logits = (xf @ router).astype(jnp.float32)
        buf, meta, (f_e, p_e) = _local_dispatch(xf, logits, cfg, E, Ep, C_loc, xl.dtype)
        # exchange: (Ep, C, D) -> (Ep/m, C*m, D)
        bufx = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufx, wg)) * jnp.einsum("ecd,edf->ecf", bufx, wu)
        outb = jnp.einsum("ecf,efd->ecd", h, wd)
        # reverse exchange: (Ep/m, C*m, D) -> (Ep, C, D)
        outb = jax.lax.all_to_all(outb, "model", split_axis=1, concat_axis=0, tiled=True)
        y = _local_combine(outb, meta, xl.dtype, D)
        # global load-balance moments (matches the scatter path exactly)
        axes = ("model",) + batch_axes
        f_g = jax.lax.pmean(f_e, axes)
        p_g = jax.lax.pmean(p_e, axes)
        aux = E * jnp.sum(f_g * p_g) / cfg.top_k
        return y.reshape(b_l, s_l, D), aux

    xspec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], "model", None)
    out = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(xspec, P(None, None), P("model", None, None), P("model", None, None), P("model", None, None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return out


def moe_ffn(p, x, cfg):
    """x: (B, S, D) -> (B, S, D), aux load-balance loss."""
    import os

    from repro.distributed import sharding as shd

    mesh = shd.active_mesh()
    Ep = p["wg"].shape[0]
    if (
        mesh is not None
        and "model" in mesh.shape
        and Ep % mesh.shape["model"] == 0
        and os.environ.get("REPRO_MOE_IMPL", "ep") == "ep"
        and x.shape[0] % max(mesh.shape.get("data", 1) * mesh.shape.get("pod", 1), 1) == 0
        and x.shape[1] % mesh.shape["model"] == 0
    ):
        y, aux = moe_ffn_ep(p, x, cfg, mesh)
        if cfg.num_shared_experts:
            y = y + _shared_expert(p, x.reshape(-1, x.shape[-1]), cfg).reshape(x.shape)
        return y, aux
    return _moe_ffn_scatter(p, x, cfg)


def _shared_expert(p, xf, cfg):
    sh = jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wu"])
    sh = constrain(sh, "batch", "ff")
    sh = sh @ p["shared_wd"]
    return jax.nn.sigmoid(xf @ p["shared_gate"].astype(xf.dtype)) * sh


def _moe_ffn_scatter(p, x, cfg):
    """Paper-faithful baseline dispatch (pure pjit scatter; §Perf cell B baseline)."""
    Bb, S, D = x.shape
    T = Bb * S
    E, Ep, k = cfg.num_experts, p["wg"].shape[0], cfg.top_k
    C = capacity(cfg, T)
    xf = x.reshape(T, D)
    xf = constrain(xf, "batch", None)

    logits = (xf @ p["router"].astype(jnp.float32)).astype(jnp.float32)  # (T, Ep)
    if Ep > E:
        logits = jnp.where(jnp.arange(Ep) >= E, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)  # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    ohk = jax.nn.one_hot(idx, Ep, dtype=jnp.float32)  # (T, k, Ep)
    f_e = ohk.sum(1).mean(0)  # fraction routed per expert
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e) / k

    # slot ranks within each expert via cumsum over the flattened choices
    flat_e = idx.reshape(-1)  # (T*k,)
    oh = jax.nn.one_hot(flat_e, Ep, dtype=jnp.int32)  # (T*k, Ep)
    ranks = jnp.cumsum(oh, axis=0) * oh  # 1-based rank where active
    slot = ranks.sum(-1) - 1  # (T*k,)
    keep = slot < C
    slot = jnp.where(keep, slot, 0)

    tok_idx = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    xg = jnp.take(xf, tok_idx, axis=0) * keep[:, None].astype(x.dtype)  # (T*k, D)

    buf = jnp.zeros((Ep, C, D), x.dtype).at[flat_e, slot].add(xg, mode="drop")
    buf = constrain(buf, "experts", None, None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    h = constrain(h, "experts", None, "ff")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wd"])
    out_buf = constrain(out_buf, "experts", None, None)

    yk = out_buf[flat_e, slot] * (gates.reshape(-1)[:, None] * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((T, D), x.dtype).at[tok_idx].add(yk, mode="drop")
    y = constrain(y, "batch", None)

    if cfg.num_shared_experts:
        sh = jax.nn.silu(xf @ p["shared_wg"]) * (xf @ p["shared_wu"])
        sh = constrain(sh, "batch", "ff")
        sh = sh @ p["shared_wd"]
        y = y + jax.nn.sigmoid(xf @ p["shared_gate"].astype(x.dtype)) * sh

    return y.reshape(Bb, S, D), aux


def block_specs(cfg, n_layers) -> dict[str, Spec]:
    st = (n_layers,)
    sp = {}
    sp.update(prefix(L.attn_specs(cfg, stack=st), "attn"))
    sp.update(prefix(L.norm_specs(cfg, stack=st), "norm1"))
    sp.update(prefix(L.norm_specs(cfg, stack=st), "norm2"))
    sp.update(prefix(moe_specs(cfg, stack=st), "moe"))
    return sp


def param_specs(cfg, max_seq: int = 0) -> dict[str, Spec]:
    sp = {}
    sp.update(prefix(L.embed_specs(cfg), "embed"))
    sp.update(prefix(block_specs(cfg, cfg.num_layers), "blocks"))
    sp.update(prefix(L.norm_specs(cfg), "final_norm"))
    return sp


def block(lp, x, cfg, *, positions, causal=True):
    h, kv = L.self_attention(subtree(lp, "attn"), L.apply_norm(lp, "norm1", x, cfg), cfg, positions=positions, causal=causal)
    x = x + h
    h, aux = moe_ffn(subtree(lp, "moe"), L.apply_norm(lp, "norm2", x, cfg), cfg)
    x = x + h
    return constrain(x, "batch", "act_seq", None), kv, aux


def backbone(params, x, cfg, *, positions, causal=True, collect_kv=False):
    blocks = subtree(params, "blocks")

    def body(carry, lp):
        y, aux_sum = carry
        y, kv, aux = block(lp, y, cfg, positions=positions, causal=causal)
        return (y, aux_sum + aux), kv if collect_kv else None

    (x, aux), kvs = jax.lax.scan(jax.checkpoint(body), (x, jnp.zeros((), jnp.float32)), blocks)
    x = L.apply_norm(params, "final_norm", x, cfg)
    return x, kvs, aux / cfg.num_layers


def hidden(params, batch, cfg):
    tokens = batch["tokens"]
    x = L.embed(subtree(params, "embed"), tokens, cfg)
    x = constrain(x, "batch", "act_seq", None)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, _, aux = backbone(params, x, cfg, positions=positions)
    return x, {"aux_loss": cfg.router_aux_weight * aux}


def forward(params, batch, cfg):
    x, aux = hidden(params, batch, cfg)
    return L.unembed(subtree(params, "embed"), x, cfg), aux


def prefill(params, batch, cfg):
    tokens = batch["tokens"]
    x = L.embed(subtree(params, "embed"), tokens, cfg)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, kvs, _ = backbone(params, x, cfg, positions=positions, collect_kv=True)
    logits = L.unembed(subtree(params, "embed"), x[:, -1:], cfg)
    return logits, {"k": kvs[0].astype(jnp.bfloat16), "v": kvs[1].astype(jnp.bfloat16)}


def decode_step(params, batch, cache, cfg):
    token, pos = batch["token"], batch["pos"]
    x = L.embed(subtree(params, "embed"), token[:, None], cfg)
    blocks = subtree(params, "blocks")

    def body(carry, xs):
        lp, ck, cv = xs
        h, kv = L.decode_self_attention(subtree(lp, "attn"), L.apply_norm(lp, "norm1", carry, cfg), cfg, cache_k=ck, cache_v=cv, pos=pos)
        y = carry + h
        h, _ = moe_ffn(subtree(lp, "moe"), L.apply_norm(lp, "norm2", y, cfg), cfg)
        return y + h, kv

    x, (nk, nv) = jax.lax.scan(body, x, (blocks, cache["k"], cache["v"]))
    x = L.apply_norm(params, "final_norm", x, cfg)
    logits = L.unembed(subtree(params, "embed"), x, cfg)
    return logits, {"k": nk, "v": nv}


def cache_specs(cfg, batch: int, seq_len: int) -> dict[str, Spec]:
    shp = (cfg.num_layers, batch, seq_len, cfg.num_kv_heads, cfg.head_dim)
    axes = ("layers", "batch", "kv_seq", "kv_heads", None)
    return {"k": Spec(shp, axes, "zeros"), "v": Spec(shp, axes, "zeros")}
