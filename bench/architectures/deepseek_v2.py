"""DeepSeek-V2 (arXiv:2405.04434; HF ``modeling_deepseek.py``): multi-head
latent attention with no query LoRA, the leading ``first_k_dense_replace``
blocks with a dense SwiGLU, the rest a softmax router over all routed
experts (greedy top-k, weights renormalised only if ``norm_topk_prob``)
beside shared experts, YaRN rope on the rope halves of queries and keys
(pairs ``(2i, 2i + 1)``), RMSNorm, an untied head.

One chip's share of an expert-parallel deployment: the configuration's
``n_routed_experts`` are the experts held here, experts
``first_held_expert`` .. of the router's ``router_experts``; assignments
to the others add nothing, here as in the program (the ``model-configs``
guide, section 4).

Implements the interface of ``bench/architectures/__init__.py``.  The
reference reads the weights in float32, runs every matmul at ``highest``
precision and attends in the published (expanded) form: keys and values
``c w_uk`` and ``c w_uv`` per head.  Its control runs every dense matmul,
the router and the experts included, on int8 weights (per output column)
and int8 activations (per row), symmetric, rounded to nearest.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import causal_pairs
from bench.reference import HIGHEST, rms_norm


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    num_layers: int
    dense_layers: int
    d_model: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    d_ff: int
    num_experts: int          # held here
    router_experts: int
    first_expert: int
    shared_experts: int
    top_k: int
    d_expert: int
    norm_topk_prob: bool
    vocab_size: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    max_position: int
    norm_eps: float
    dtype: str
    #: the control: every dense matmul on int8 weights (per output column)
    #: and int8 activations (per row), symmetric, rounded to nearest
    int8_matmuls: bool = False

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        if c.get("q_lora_rank") is not None:
            raise ValueError("query LoRA is not implemented")
        if float(c.get("routed_scaling_factor", 1.0)) != 1.0:
            raise ValueError("routed_scaling_factor other than 1")
        y = c["rope_scaling"]
        if y.get("type") != "yarn":
            raise ValueError(f"rope scaling {y.get('type')!r}")
        return cls(
            num_layers=int(c["num_hidden_layers"]),
            dense_layers=int(c["first_k_dense_replace"]),
            d_model=int(c["hidden_size"]),
            num_heads=int(c["num_attention_heads"]),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_dim=int(c["qk_nope_head_dim"]),
            qk_rope_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            d_ff=int(c["intermediate_size"]),
            num_experts=int(c["n_routed_experts"]),
            router_experts=int(c["router_experts"]),
            first_expert=int(c["first_held_expert"]),
            shared_experts=int(c["n_shared_experts"]),
            top_k=int(c["num_experts_per_tok"]),
            d_expert=int(c["moe_intermediate_size"]),
            norm_topk_prob=bool(c["norm_topk_prob"]),
            vocab_size=int(c["vocab_size"]),
            rope_theta=float(c["rope_theta"]),
            yarn_factor=float(y["factor"]),
            yarn_original=int(y["original_max_position_embeddings"]),
            yarn_beta_fast=float(y["beta_fast"]),
            yarn_beta_slow=float(y["beta_slow"]),
            yarn_mscale=float(y["mscale"]),
            yarn_mscale_all_dim=float(y["mscale_all_dim"]),
            max_position=int(c["max_position_embeddings"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=str(c["torch_dtype"]),
        )


def control(a: Arch) -> Arch:
    return dataclasses.replace(a, int8_matmuls=True)


def program_config(a: Arch, name: str):
    """The program's ``ModelConfig`` for the sizes of ``a``."""
    from bench import system  # noqa: F401  (puts the program on the path)
    from repro.configs.base import ModelConfig, Yarn

    return ModelConfig(
        name=name, family="moe", num_layers=a.num_layers,
        d_model=a.d_model, num_heads=a.num_heads, num_kv_heads=a.num_heads,
        d_ff=a.d_ff, vocab_size=a.vocab_size, mla=True,
        kv_lora_rank=a.kv_lora_rank, qk_nope_dim=a.qk_nope_dim,
        qk_rope_dim=a.qk_rope_dim, v_head_dim=a.v_head_dim, moe=True,
        n_routed_experts=a.num_experts, router_experts=a.router_experts,
        expert_offset=a.first_expert, n_shared_experts=a.shared_experts,
        top_k=a.top_k, moe_d_ff=a.d_expert,
        first_dense_layers=a.dense_layers, norm_topk_prob=a.norm_topk_prob,
        router_aux_coef=0.0, rope_theta=a.rope_theta,
        rope_scaling=Yarn(
            factor=a.yarn_factor, original_max_position=a.yarn_original,
            beta_fast=a.yarn_beta_fast, beta_slow=a.yarn_beta_slow,
            mscale=a.yarn_mscale, mscale_all_dim=a.yarn_mscale_all_dim),
        norm_eps=a.norm_eps, max_position=a.max_position,
        tie_embeddings=False, dtype=a.dtype,
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init_weights(key, a: Arch):
    """One member's weights from ``key``, in the program's layout: the
    leading dense blocks stacked under ``dense_blocks``, the expert blocks
    under ``blocks``.

    Matrices are normal with std fan_in^-0.5 (the embedding 0.02), the
    router float32; norm scales are 1 + 0.1 N(0, 1), so that every
    parameter the reference reads carries a value of its own."""
    dtype = jnp.dtype(a.dtype)
    D, H, r = a.d_model, a.num_heads, a.kv_lora_rank
    ks = iter(jax.random.split(key, 64))

    def mat(shape, std=None, dt=dtype):
        std = shape[-2] ** -0.5 if std is None else std
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dt)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def swiglu(n, F, lead=()):
        return {"w1": mat((n,) + lead + (D, F)),
                "w3": mat((n,) + lead + (D, F)),
                "w2": mat((n,) + lead + (F, D))}

    def stack(n, mlp):
        return {
            "ln1": {"scale": scale((n, D))},
            "ln2": {"scale": scale((n, D))},
            "attn": {
                "wq": mat((n, D, H * (a.qk_nope_dim + a.qk_rope_dim))),
                "w_dkv": mat((n, D, r)),
                "w_krope": mat((n, D, a.qk_rope_dim)),
                "kv_norm": {"scale": scale((n, r))},
                "w_uk": mat((n, r, H * a.qk_nope_dim)),
                "w_uv": mat((n, r, H * a.v_head_dim)),
                "wo": mat((n, H * a.v_head_dim, D)),
            },
            "mlp": mlp,
        }

    n_moe = a.num_layers - a.dense_layers
    w = {
        "embed": {"tok": mat((a.vocab_size, D), std=0.02)},
        "final_norm": {"scale": scale((D,))},
        "lm_head": {"w": mat((D, a.vocab_size))},
        "blocks": stack(n_moe, {
            "router": mat((n_moe, D, a.router_experts), dt=jnp.float32),
            "experts": swiglu(n_moe, a.d_expert, (a.num_experts,)),
            "shared": swiglu(n_moe, a.d_expert * a.shared_experts),
        }),
    }
    if a.dense_layers:
        w["dense_blocks"] = stack(a.dense_layers,
                                  swiglu(a.dense_layers, a.d_ff))
    return w


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _int8(x, axis):
    """x rounded to symmetric int8 along ``axis`` (straight through for
    gradients)."""
    s = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
        / 127.0)
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


def _mm(x, w, a: Arch):
    if a.int8_matmuls:
        x, w = _int8(x, -1), _int8(w, -2)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer(w, i):
    return jax.tree_util.tree_map(lambda x: x[i], w)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(a: Arch) -> np.ndarray:
    """Inverse frequencies of the rope pairs under YaRN, in float64:
    ``f_i = theta^(-2i/d)``, pairs below ``low`` kept, above ``high``
    divided by the factor, a linear ramp between."""
    d = a.qk_rope_dim
    f = a.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def pair(rotations):
        return (d * math.log(a.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(a.rope_theta)))

    low = max(math.floor(pair(a.yarn_beta_fast)), 0)
    high = min(math.ceil(pair(a.yarn_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return f / a.yarn_factor * ramp + f * (1.0 - ramp)


def softmax_scale(a: Arch) -> float:
    return ((a.qk_nope_dim + a.qk_rope_dim) ** -0.5
            * yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim) ** 2)


def _rope(x, pos, a: Arch):
    """x: (T, heads, d): pairs (2i, 2i+1) rotated by pos * inv_freq_i,
    results ordered evens then odds (HF ``apply_rotary_pos_emb``)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(a), jnp.float32)[None]
    m = (yarn_mscale(a.yarn_factor, a.yarn_mscale)
         / yarn_mscale(a.yarn_factor, a.yarn_mscale_all_dim))
    cos, sin = m * jnp.cos(ang)[:, None], m * jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attend(w, x, a: Arch, q_block: int):
    """A block's first half over a whole sequence x: (T, D): x plus its
    causal latent attention, ``q_block`` queries at a time."""
    T = x.shape[0]
    H = a.num_heads
    at = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], a.norm_eps)
    q = _mm(h, at["wq"], a).reshape(T, H, a.qk_nope_dim + a.qk_rope_dim)
    q_nope, q_pe = q[..., :a.qk_nope_dim], q[..., a.qk_nope_dim:]
    c = rms_norm(_mm(h, at["w_dkv"], a), at["kv_norm"]["scale"], a.norm_eps)
    k_pe = _mm(h, at["w_krope"], a)[:, None]
    pos = jnp.arange(T)
    q_pe, k_pe = _rope(q_pe, pos, a), _rope(k_pe, pos, a)[:, 0]
    k_nope = _mm(c, at["w_uk"], a).reshape(T, H, a.qk_nope_dim)
    v = _mm(c, at["w_uv"], a).reshape(T, H, a.v_head_dim)
    s = softmax_scale(a)
    nq = -(-T // q_block)
    pad = nq * q_block - T

    def block(args):
        qn, qp, qpos = args
        sc = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=HIGHEST)
              + jnp.einsum("thd,sd->hts", qp, k_pe, precision=HIGHEST)) * s
        sc = jnp.where(jnp.arange(T)[None, None] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(sc, -1), v,
                          precision=HIGHEST)

    def blocks(z):  # (T, ...) -> (nq, q_block, ...)
        z = jnp.pad(z, [(0, pad)] + [(0, 0)] * (z.ndim - 1))
        return z.reshape((nq, q_block) + z.shape[1:])

    o = jax.lax.map(block, (blocks(q_nope), blocks(q_pe), blocks(pos)))
    o = o.reshape(nq * q_block, -1)[:T]
    return x + _mm(o, at["wo"], a)


def _swiglu(m, h, a: Arch):
    return _mm(jax.nn.silu(_mm(h, m["w1"], a)) * _mm(h, m["w3"], a),
               m["w2"], a)


def moe(m, h, a: Arch):
    """The expert layer over h: (T, D): each held expert weighted by its
    router probability where it is among the token's top_k of all the
    router's experts (renormalised over them if ``norm_topk_prob``), plus
    the shared experts."""
    probs = jax.nn.softmax(_mm(h, m["router"], a), -1)
    top_w, top_i = jax.lax.top_k(probs, a.top_k)
    if a.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    out = _swiglu(m["shared"], h, a)
    for e in range(a.num_experts):
        gate = jnp.sum(jnp.where(top_i == a.first_expert + e, top_w, 0.0), -1)
        out = out + gate[:, None] * _swiglu(_layer(m["experts"], e), h, a)
    return out


def hidden(params, tokens, a: Arch, q_block: int = 256):
    """Final-normed hidden states (T, D) of one sequence."""
    x = params["embed"]["tok"][tokens]
    for name, n, dense in (("dense_blocks", a.dense_layers, True),
                           ("blocks", a.num_layers - a.dense_layers, False)):
        for i in range(n):
            w = _layer(params[name], i)
            x = attend(w, x, a, q_block)
            h = rms_norm(x, w["ln2"]["scale"], a.norm_eps)
            x = x + (_swiglu(w["mlp"], h, a) if dense else moe(w["mlp"], h, a))
    return rms_norm(x, params["final_norm"]["scale"], a.norm_eps)


def logits_at(params, tokens, rows, a: Arch):
    """Logits (len(rows), V) of one sequence at positions ``rows``."""
    h = hidden(params, tokens, a)
    return _mm(h[rows], params["lm_head"]["w"], a)


def loss(params, tokens, a: Arch):
    """Mean next-token cross entropy over a batch (B, S) of sequences."""
    def one(t):
        lg = _mm(hidden(params, t, a, q_block=t.shape[0])[:-1],
                 params["lm_head"]["w"], a)
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.mean(jnp.take_along_axis(lp, t[1:, None], -1))
    return jnp.mean(jax.vmap(one)(tokens))


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------


def latent_width(a: Arch) -> int:
    """Elements of one cached position: the latent and the rope key."""
    return a.kv_lora_rank + a.qk_rope_dim


def attn_matmul_params(a: Arch) -> int:
    """Matmul weights one token meets in a block's attention: q, the
    latent and rope key, the query absorbed through ``w_uk``, the latent
    output expanded through ``w_uv``, and o."""
    D, H, r = a.d_model, a.num_heads, a.kv_lora_rank
    return (D * H * (a.qk_nope_dim + a.qk_rope_dim) + D * latent_width(a)
            + r * H * (a.qk_nope_dim + a.v_head_dim) + H * a.v_head_dim * D)


def moe_matmul_params(a: Arch) -> int:
    """Matmul weights one token meets in an expert layer: the router, the
    shared experts and, as the expected share of its top_k that are held
    here, ``top_k * num_experts / router_experts`` routed experts (1.5 of
    DeepSeek-V2-Lite's 6 when 16 of 64 are held)."""
    D = a.d_model
    held = a.top_k * a.num_experts / a.router_experts
    return int(D * a.router_experts
               + 3 * D * a.d_expert * (a.shared_experts + held))


def matmul_params(a: Arch) -> int:
    """All the blocks' matmul weights one token meets."""
    n_moe = a.num_layers - a.dense_layers
    return (a.num_layers * attn_matmul_params(a)
            + a.dense_layers * 3 * a.d_model * a.d_ff
            + n_moe * moe_matmul_params(a))


def head_params(a: Arch) -> int:
    return a.d_model * a.vocab_size


def attention_flops(a: Arch, pairs: int) -> int:
    """Latent attention of one layer over ``pairs`` (query, key) pairs:
    each head's score over the latent and rope key, and its weighted sum
    of latents."""
    return 2 * a.num_heads * (2 * a.kv_lora_rank + a.qk_rope_dim) * pairs


def train_step(a: Arch, batch: int, seq: int) -> int:
    """Model FLOPs of one member's training step: forward and backward
    (3x the forward) of every matmul over all tokens, and of causal
    attention."""
    tokens = batch * seq
    matmul = 2 * (matmul_params(a) + head_params(a))
    attn = a.num_layers * attention_flops(a, causal_pairs(0, seq)) * batch
    return 3 * (matmul * tokens + attn)


def prefill_chunk(a: Arch, start: int, length: int) -> int:
    """A prompt chunk of ``length`` tokens at position ``start``: every
    block for each token, causal attention over the context so far, and
    the head for the chunk's last token only."""
    matmul = 2 * matmul_params(a) * length
    attn = a.num_layers * attention_flops(a, causal_pairs(start, length))
    return matmul + attn + 2 * head_params(a)


def decode_step(a: Arch, lengths: Iterable[int]) -> int:
    """One decode step of the active slots, whose contexts (the new token
    included) are ``lengths``."""
    lengths = list(lengths)
    per_token = 2 * (matmul_params(a) + head_params(a))
    attn = a.num_layers * sum(attention_flops(a, n) for n in lengths)
    return per_token * len(lengths) + attn


def decode_kernel_flops(a: Arch, lengths: Iterable[int]) -> int:
    """The latent kernel's FLOPs in one decode step, one call a layer:
    2 * H * (r + rope + r) per cached position."""
    return a.num_layers * sum(attention_flops(a, n) for n in lengths)


def decode_kernel_bytes(a: Arch, dmas: int, slots: int, page_size: int
                        ) -> int:
    """The latent kernel's bytes in one decode step, one call a layer:
    ``dmas`` latent pages of ``page_size`` rows of ``r + rope`` elements,
    and each slot's float32 queries read and outputs written once (both
    ``(H, r + rope)``)."""
    itemsize = jnp.dtype(a.dtype).itemsize
    pages = dmas * page_size * latent_width(a) * itemsize
    qo = 2 * slots * a.num_heads * latent_width(a) * 4
    return a.num_layers * (pages + qo)
