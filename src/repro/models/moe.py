"""Mixture-of-Experts layer.

Two execution paths with identical routing semantics:

* `moe_dense`     -- every expert computed for every token, outputs masked
                     by the top-k router weights. Exact; O(E/topk) FLOP
                     overhead. Used for smoke tests and as the oracle in
                     property tests.
* `moe_capacity`  -- production path: capacity-factor token dispatch into
                     per-expert buffers (scatter), expert matmuls, combine.
                     Tokens over capacity are dropped (standard TPU MoE).
                     Under the production mesh the expert axis is sharded
                     ('model' = EP) and XLA lowers dispatch/combine into
                     all-to-alls; see distributed/sharding.py.

Routing: softmax router (fp32), top-k, renormalized weights; optional
shared experts (always on) and a dense residual branch (arctic) are
handled in transformer.py, not here.

Expert-count padding: configs whose n_experts doesn't divide the EP axis
are padded with dummy experts whose router logits are -inf (never
selected); `n_experts_padded` reports the padded count.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.api import shard_hint
from repro.models.layers import dense_init, dtype_of

Array = jax.Array


def padded_expert_count(n_experts: int, ep: int = 16) -> int:
    return int(math.ceil(n_experts / ep) * ep)


def init_moe(key, cfg, dtype, ep: int | None = None):
    d, k = cfg.d_model, cfg.n_experts_active
    ff = cfg.moe_d_ff or cfg.d_ff
    E = padded_expert_count(cfg.n_experts, ep or cfg.ep_axis)
    ks = jax.random.split(key, 4)
    gated = cfg.activation in ("swiglu", "geglu")
    p = {
        "router": dense_init(ks[0], (d, E), scale=0.02, dtype=jnp.float32),
        "w_in": dense_init(ks[1], (E, d, ff), dtype=dtype),
        "w_out": dense_init(
            ks[2], (E, ff, d), scale=1.0 / math.sqrt(ff * 2 * cfg.n_layers),
            dtype=dtype,
        ),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (E, d, ff), dtype=dtype)
    return p


def _route(p, x: Array, cfg) -> Tuple[Array, Array]:
    """Returns (weights [T,k], idx [T,k]); pads masked to -inf."""
    E = p["router"].shape[1]
    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    if E > cfg.n_experts:  # mask padded experts out of routing
        pad_mask = jnp.arange(E) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.n_experts_active)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9
    )
    return weights, idx


def _expert_ffn(p, h: Array, cfg, cd) -> Array:
    """h: [..., E, C, D] blocked per expert -> expert MLP."""
    up = jnp.einsum("ecd,edf->ecf", h, p["w_in"].astype(cd))
    if "w_gate" in p:
        g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(cd))
        act = jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.gelu(g)
        up = act * up
    else:
        up = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", up, p["w_out"].astype(cd))


def moe_dense(p, x: Array, cfg) -> Array:
    """Exact MoE: all experts on all tokens (tiny configs only)."""
    B, S, D = x.shape
    cd = dtype_of(cfg.compute_dtype)
    T = B * S
    xt = x.reshape(T, D)
    weights, idx = _route(p, xt, cfg)
    E = p["router"].shape[1]
    up = jnp.einsum("td,edf->tef", xt, p["w_in"].astype(cd))
    if "w_gate" in p:
        g = jnp.einsum("td,edf->tef", xt, p["w_gate"].astype(cd))
        act = jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.gelu(g)
        up = act * up
    else:
        up = jax.nn.gelu(up)
    y_all = jnp.einsum("tef,efd->ted", up, p["w_out"].astype(cd))  # [T,E,D]
    gate = jnp.zeros((T, E), jnp.float32)
    gate = jax.vmap(lambda g_row, i, w: g_row.at[i].add(w))(gate, idx, weights)
    y = jnp.einsum("ted,te->td", y_all.astype(jnp.float32), gate)
    return y.reshape(B, S, D).astype(x.dtype)


def moe_capacity(
    p, x: Array, cfg, *, capacity_factor: float = 1.25
) -> Array:
    """Capacity-based dispatch/combine (production path).

    [B,S,D] -> flatten T tokens -> top-k route -> position-in-expert via
    cumsum -> scatter into [E, C, D] -> expert FFN -> gather back.
    Token (t, slot j) beyond expert capacity C is dropped (weight stays,
    renormalization keeps output scale).
    """
    B, S, D = x.shape
    cd = dtype_of(cfg.compute_dtype)
    T = B * S
    k = cfg.n_experts_active
    E = p["router"].shape[1]
    C = int(max(1, math.ceil(T * k * capacity_factor / E)))

    xt = x.reshape(T, D)
    weights, idx = _route(p, xt, cfg)  # [T,k]

    flat_e = idx.reshape(-1)  # [T*k] expert of each (token, slot)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T*k, E]
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    # scatter tokens into expert buffers
    buf = jnp.zeros((E, C, D), cd)
    tok_of = jnp.repeat(jnp.arange(T), k)
    e_idx = jnp.where(keep, flat_e, 0)
    c_idx = jnp.where(keep, pos, 0)
    vals = jnp.where(keep[:, None], xt[tok_of].astype(cd), 0)
    buf = buf.at[e_idx, c_idx].add(vals, mode="drop")
    buf = shard_hint(buf, "moe_buf")

    out_buf = _expert_ffn(p, buf, cfg, cd)  # [E, C, D]
    out_buf = shard_hint(out_buf, "moe_buf")

    # combine: gather each (token, slot)'s output, weight, sum over k
    gathered = out_buf[e_idx, c_idx]  # [T*k, D]
    w_flat = weights.reshape(-1) * keep.astype(jnp.float32)
    y = jnp.zeros((T, D), jnp.float32)
    y = y.at[tok_of].add(gathered.astype(jnp.float32) * w_flat[:, None])
    return y.reshape(B, S, D).astype(x.dtype)


def moe_ep_shardmap(
    p, x: Array, cfg, mesh, dp_axes, ep_axis: str,
    *, capacity_factor: float = 1.25,
) -> Array:
    """Expert-parallel MoE via shard_map (GShard-style, TPU-native).

    Tokens are sharded over (dp x ep): each device routes its local
    tokens, scatters them into per-expert buffers, exchanges expert shards
    with one all_to_all over the 'model' axis, runs its local experts
    (weights FSDP-gathered over 'data' just-in-time), and reverses the
    exchange. Token count per device is T/(dp*ep); the dispatch tensors
    never exceed [E, C_loc, D] with C_loc = ceil(T_loc*k*cf/E).

    Falls back to `moe_capacity` shapes when the sequence doesn't divide
    the ep axis (e.g. decode steps with S=1) -- see apply_moe.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, S, D = x.shape
    E = p["router"].shape[1]
    k = cfg.n_experts_active
    ep = mesh.shape[ep_axis]
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    cd = dtype_of(cfg.compute_dtype)
    T_loc = (B // dp) * (S // ep)
    C_loc = int(max(1, math.ceil(T_loc * k * capacity_factor / E)))
    dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    gated = "w_gate" in p

    def block(router, w_in, w_gate, w_out, xb):
        # router [D,E] replicated; w_in [E/ep, D/dp, F]; xb [B/dp, S/ep, D]
        b_loc, s_loc, _ = xb.shape
        xt = xb.reshape(T_loc, D)
        logits = jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), router.astype(jnp.float32)
        )
        if E > cfg.n_experts:
            pad_mask = jnp.arange(E) >= cfg.n_experts
            logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(
            jnp.sum(weights, -1, keepdims=True), 1e-9
        )

        flat_e = idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0) - onehot, flat_e[:, None], axis=1
        )[:, 0]
        keep = pos < C_loc
        tok_of = jnp.repeat(jnp.arange(T_loc), k)
        e_idx = jnp.where(keep, flat_e, 0)
        c_idx = jnp.where(keep, pos, 0)
        vals = jnp.where(keep[:, None], xt[tok_of].astype(cd), 0)
        buf = jnp.zeros((E, C_loc, D), cd).at[e_idx, c_idx].add(
            vals, mode="drop"
        )

        # exchange expert shards: [E, C_loc, D] -> [E/ep, ep*C_loc, D]
        buf = jax.lax.all_to_all(
            buf, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )

        # FSDP-gather local experts' weights over 'data'
        w_in_full = jax.lax.all_gather(
            w_in, dp_axes, axis=1, tiled=True
        ) if dp_axes else w_in
        w_out_full = jax.lax.all_gather(
            w_out, dp_axes, axis=2, tiled=True
        ) if dp_axes else w_out
        up = jnp.einsum("ecd,edf->ecf", buf, w_in_full.astype(cd))
        if gated:
            w_g_full = jax.lax.all_gather(
                w_gate, dp_axes, axis=1, tiled=True
            ) if dp_axes else w_gate
            g = jnp.einsum("ecd,edf->ecf", buf, w_g_full.astype(cd))
            act = jax.nn.silu(g) if cfg.activation == "swiglu" else \
                jax.nn.gelu(g)
            up = act * up
        else:
            up = jax.nn.gelu(up)
        out_buf = jnp.einsum("ecf,efd->ecd", up, w_out_full.astype(cd))

        # reverse exchange and combine locally
        out_buf = jax.lax.all_to_all(
            out_buf, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )
        gathered = out_buf[e_idx, c_idx]
        w_flat = weights.reshape(-1) * keep.astype(jnp.float32)
        y = jnp.zeros((T_loc, D), jnp.float32).at[tok_of].add(
            gathered.astype(jnp.float32) * w_flat[:, None]
        )
        return y.reshape(b_loc, s_loc, D).astype(xb.dtype)

    w_gate_arg = p.get("w_gate", p["w_in"])  # placeholder when ungated
    fn = jax.shard_map(
        block,
        mesh=mesh,
        in_specs=(
            P(None, None),                      # router replicated
            P(ep_axis, dp_spec, None),          # w_in  [E, D, F]
            P(ep_axis, dp_spec, None),          # w_gate
            P(ep_axis, None, dp_spec),          # w_out [E, F, D]
            P(dp_spec, ep_axis, None),          # x tokens over dp x ep
        ),
        out_specs=P(dp_spec, ep_axis, None),
        check_vma=False,
    )
    return fn(p["router"], p["w_in"], w_gate_arg, p["w_out"], x)


def apply_moe(p, x: Array, cfg) -> Array:
    if cfg.moe_path == "dense":
        return moe_dense(p, x, cfg)
    ctx = None
    try:
        from repro.distributed.api import mesh_context
        ctx = mesh_context()
    except Exception:
        ctx = None
    if ctx is not None:
        mesh, dp_axes, ep_axis = ctx
        ep = mesh.shape[ep_axis]
        dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
        B, S = x.shape[0], x.shape[1]
        if B % max(dp, 1) == 0 and S % ep == 0:
            return moe_ep_shardmap(
                p, x, cfg, mesh, dp_axes, ep_axis,
                capacity_factor=cfg.moe_capacity_factor,
            )
    return moe_capacity(p, x, cfg, capacity_factor=cfg.moe_capacity_factor)
