"""Mixture-of-Experts MLP block (top-k routing, capacity-bounded dispatch).

Dispatch is sort-free scatter-based (MegaBlocks-style positions computed with
a cumsum over one-hot expert assignment *counts*, not a (T,E,Cap) one-hot
tensor): memory stays O(T·k + E·Cap·D), so 65k tokens/device × 16 experts is
fine. Tokens overflowing an expert's capacity are dropped (standard GShard
semantics); the residual stream carries them unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import dense_init


def moe_param_specs(cfg: ModelConfig, dtype) -> dict:
    sds = jax.ShapeDtypeStruct
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    p = {
        "router": sds((d, e), jnp.float32),
        "w1": sds((e, d, f), dtype),
        "w2": sds((e, f, d), dtype),
    }
    if cfg.gated_mlp:
        p["w3"] = sds((e, d, f), dtype)
    return p


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "w1": dense_init(ks[1], (e, d, f), d, dtype),
        "w2": dense_init(ks[2], (e, f, d), f, dtype),
    }
    if cfg.gated_mlp:
        p["w3"] = dense_init(ks[3], (e, d, f), d, dtype)
    return p


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe_block(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B,S,D) -> (B,S,D). Top-k routing with capacity dropping.

    ``cfg.moe_dispatch == "local"`` runs the dispatch *per device* inside a
    shard_map (tokens stay on their batch shard; position cumsum is local;
    expert FFN is TP-sharded on d_ff with one row-parallel psum) — under
    GSPMD the global-cumsum dispatch otherwise forces all-reduces of the
    whole (E, Cap, D) buffer every layer (measured: 187 s/step collective
    term for dbrx prefill; see EXPERIMENTS.md §Perf)."""
    if cfg.moe_dispatch == "local":
        from repro.models import meshctx
        mesh = meshctx.get_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            return _moe_block_local(p, x, cfg, mesh)
    return _moe_block_global(p, x, cfg)


def _moe_block_local(p: dict, x: jax.Array, cfg: ModelConfig, mesh):

    from jax.sharding import PartitionSpec as P
    from repro.models.meshctx import replica_axes

    rep = replica_axes(mesh)
    dp = rep if len(rep) > 1 else rep[0]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_size = 1
    for a in rep:
        dp_size *= sizes[a]
    bspec = dp if x.shape[0] % dp_size == 0 and x.shape[0] >= dp_size \
        else None

    def body(xl, router, w1, w2, *w3):
        pl = {"router": router, "w1": w1, "w2": w2}
        if w3:
            pl["w3"] = w3[0]
        out = _moe_block_global(pl, xl, cfg)          # local tokens/capacity
        return jax.lax.psum(out, "model")             # row-parallel combine

    in_specs = [P(bspec, None, None), P(), P(None, None, "model"),
                P(None, "model", None)]
    args = [x, p["router"], p["w1"], p["w2"]]
    if cfg.gated_mlp:
        in_specs.append(P(None, None, "model"))
        args.append(p["w3"])
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(bspec, None, None), check_vma=False)
    return fn(*args)


def _moe_block_global(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    m = cfg.moe
    cd = cfg.compute_dtype
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    cap = expert_capacity(t, cfg)

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # (T,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalize

    # Position of assignment (t, j) within its expert's buffer: rank order is
    # (slot j, then token t) — flatten to (k*T,) with j-major so that lower
    # slots get capacity first, then count per expert with a masked cumsum.
    flat_e = top_e.T.reshape(-1)                               # (k*T,) j-major
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # (kT, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot             # exclusive
    flat_pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < cap

    # Scatter tokens into (E, Cap, D) buffers (dropped tokens go nowhere).
    buf = jnp.zeros((e, cap, d), cd)
    src = jnp.repeat(xt[None], k, axis=0).reshape(-1, d).astype(cd)
    e_idx = jnp.where(keep, flat_e, e)          # OOB row -> dropped
    p_idx = jnp.where(keep, flat_pos, 0)
    buf = buf.at[e_idx, p_idx].add(src, mode="drop")

    # Expert FFN, batched over experts.
    h = jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(cd))
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(cd))
        h = jax.nn.silu(h) * g
    else:
        h = jax.nn.gelu(h)
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(cd))

    # Gather back and combine with routing weights.
    gathered = out_buf[e_idx, p_idx]                           # (kT, D)
    flat_w = top_p.T.reshape(-1).astype(jnp.float32)
    gathered = gathered.astype(jnp.float32) * jnp.where(keep, flat_w, 0.0)[:, None]
    combined = jnp.sum(gathered.reshape(k, t, d), axis=0)
    return combined.reshape(b, s, d).astype(x.dtype)
