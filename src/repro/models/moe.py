"""Mixture-of-Experts layer: top-k router over the held experts.

The router scores all ``cfg.resolved_router_experts`` experts (softmax in
float32, greedy top-k, weights renormalised over the chosen ones unless
``cfg.norm_topk_prob`` is false); this model holds experts
``expert_offset .. expert_offset + n_routed_experts - 1`` of them, as one
chip of an expert-parallel deployment does.  Each token's assignments to
held experts are computed, and those to experts held elsewhere add
nothing: the exchange with the chips that hold them is not modelled.

Two dispatches:

* drop-free (the default): the assignments are sorted by expert and run
  as grouped matmuls (``jax.lax.ragged_dot``), every one of them, so a
  token's output does not depend on its batchmates;
* capacity (GShard/MaxText style), only where the in-model sharding hints
  are active (``repro.sharding.hints``: a mesh with an expert axis):
  each expert processes a fixed-capacity ``(G, E, C, D)`` buffer pinned
  expert-parallel, and overflow falls back to the residual path.
  ``cfg.moe_impl == "global"`` makes one group of all B·T tokens,
  ``"grouped"`` one group per batch row (buffers stay data-local and the
  exchange lowers to all-to-all).

Shared experts (DeepSeek-V2 / Kimi-K2 style) run densely on every token.
Device scopes: ``moe`` holds ``route``, ``experts`` and ``shared``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, param_dtype, swiglu, swiglu_init
from repro.sharding import hints
from repro.sharding.hints import constrain


def moe_init(key, cfg: ModelConfig):
    dtype = param_dtype(cfg)
    E, D, F = cfg.n_routed_experts, cfg.d_model, cfg.resolved_moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, cfg.resolved_router_experts),
                             jnp.float32),
        "experts": {
            "w1": dense_init(ks[1], (E, D, F), dtype),
            "w3": dense_init(ks[2], (E, D, F), dtype),
            "w2": dense_init(ks[3], (E, F, D), dtype),
        },
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = swiglu_init(ks[4], D, F * cfg.n_shared_experts, dtype)
    return p


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(num_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_routed_experts)
    return max(8, -(-c // 8) * 8)  # >=8, rounded up to a multiple of 8


def moe_apply(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out, aux_loss). Residual is added by the caller."""
    B, T, D = x.shape
    with jax.named_scope("moe"):
        if hints.active():
            xg = x if cfg.moe_impl == "grouped" and B > 1 else x.reshape(
                1, B * T, D)
            out, aux = _dispatch_grouped(p, cfg, xg)
        else:
            out, aux = _dispatch_dropfree(p, cfg, x.reshape(B * T, D))
        out = out.reshape(B, T, D)
        if cfg.n_shared_experts > 0:
            with jax.named_scope("shared"):
                out = out + swiglu(p["shared"], x.reshape(B * T, D)).reshape(
                    B, T, D)
    return out, aux


def _route(p, cfg: ModelConfig, x):
    """x: (..., D) -> (probs (..., E_router), top_w, top_i (..., K)) and
    the Switch load-balance loss over all tokens."""
    E, K = cfg.resolved_router_experts, cfg.top_k
    with jax.named_scope("route"):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)
        top_w, top_i = jax.lax.top_k(probs, K)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        flat = probs.reshape(-1, E)
        ce = jnp.zeros((E,), jnp.float32).at[top_i[..., 0].reshape(-1)].add(
            1.0 / flat.shape[0])
        aux = E * jnp.sum(jnp.mean(flat, axis=0) * ce)
    return top_w, top_i, aux


def _dispatch_dropfree(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """x: (N, D) -> (out (N, D), aux): every assignment to a held expert,
    sorted by expert and run through grouped matmuls."""
    N, D = x.shape
    E, K = cfg.n_routed_experts, cfg.top_k
    top_w, top_i, aux = _route(p, cfg, x)
    with jax.named_scope("experts"):
        local = top_i.reshape(-1) - cfg.expert_offset
        held = (local >= 0) & (local < E)
        # assignments held elsewhere sort last, past every group
        order = jnp.argsort(jnp.where(held, local, E))
        sizes = jnp.zeros((E,), jnp.int32).at[local].add(
            held.astype(jnp.int32), mode="drop")
        tok = order // K
        xs = x[tok]
        w = p["experts"]
        h = jax.nn.silu(jax.lax.ragged_dot(xs, w["w1"], sizes)) * \
            jax.lax.ragged_dot(xs, w["w3"], sizes)
        y = jax.lax.ragged_dot(h, w["w2"], sizes)
        gate = jnp.where(held, top_w.reshape(-1), 0.0)[order]
        y = jnp.where(held[order][:, None], y * gate[:, None].astype(y.dtype),
                      0)
        out = jnp.zeros((N, D), y.dtype).at[tok].add(y)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def _dispatch_grouped(p, cfg: ModelConfig, x) -> Tuple[jax.Array, jax.Array]:
    """x: (G, Tg, D) token groups -> (out (G, Tg, D), aux)."""
    G, Tg, D = x.shape
    E, K = cfg.n_routed_experts, cfg.top_k
    C = capacity(cfg, Tg)
    TK = Tg * K
    g_idx = jnp.arange(G)[:, None]

    top_w, top_i, aux = _route(p, cfg, x)  # (G, Tg, K)
    top_i = top_i - cfg.expert_offset
    top_w = jnp.where((top_i >= 0) & (top_i < E), top_w, 0.0)
    top_i = jnp.clip(top_i, 0, E - 1)

    # flatten (token, slot) assignments and sort by expert id, per group
    flat_e = top_i.reshape(G, TK)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(Tg), K)[None], (G, TK)
    )
    flat_w = top_w.reshape(G, TK)
    order = jnp.argsort(flat_e, axis=1)  # stable
    se = jnp.take_along_axis(flat_e, order, 1)
    st = jnp.take_along_axis(flat_t, order, 1)
    sw = jnp.take_along_axis(flat_w, order, 1)

    counts = jnp.zeros((G, E), jnp.int32).at[g_idx, se].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((G, 1), jnp.int32), jnp.cumsum(counts, axis=1)[:, :-1]], axis=1
    )
    pos_in_e = jnp.arange(TK)[None] - jnp.take_along_axis(starts, se, 1)
    keep = pos_in_e < C
    slot = se * C + jnp.where(keep, pos_in_e, 0)

    xt = jnp.take_along_axis(x, st[..., None], 1)  # (G, TK, D)
    buf = jnp.zeros((G, E * C, D), x.dtype).at[g_idx, slot].add(
        jnp.where(keep[..., None], xt, 0).astype(x.dtype)
    )
    # two-stage reshard: build data-local (the dispatch scatter never
    # crosses devices; model-axis replicas build redundant copies, which is
    # cheap), then slice experts onto the model axis so the expert einsum
    # is fully expert-parallel.
    buf = constrain(buf.reshape(G, E, C, D), "moe_buffer_local")
    buf = constrain(buf, "moe_buffer")

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["experts"]["w1"])) * jnp.einsum(
        "gecd,edf->gecf", buf, p["experts"]["w3"]
    )
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["experts"]["w2"])
    out_buf = constrain(constrain(out_buf, "moe_buffer"), "moe_buffer_local")
    out_buf = out_buf.reshape(G, E * C, D)

    contrib = jnp.take_along_axis(out_buf, slot[..., None], 1)
    contrib = contrib * (sw * keep)[..., None].astype(x.dtype)
    out = jnp.zeros((G, Tg, D), x.dtype).at[g_idx, st].add(contrib)
    return out, aux.astype(jnp.float32)
