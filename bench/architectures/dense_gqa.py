"""The dense decoder of the Qwen families: RMSNorm, rotary embeddings
(rotate-half, base ``rope_theta``), grouped-query causal attention with
optional q/k/v bias and per-head q/k RMSNorm, SwiGLU, an untied head.

Implements the interface of ``bench/architectures/__init__.py``.  The
reference reads the weights in float32 and runs every matmul at
``highest`` precision; its control runs every dense matmul on int8
weights (per output column) and int8 activations (per row), symmetric,
rounded to nearest.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable

import jax
import jax.numpy as jnp

from bench.flops import causal_pairs
from bench.reference import HIGHEST, rms_norm, rope


@dataclasses.dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qk_norm: bool
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    #: the control: every dense matmul on int8 weights (per output column)
    #: and int8 activations (per row), symmetric, rounded to nearest
    int8_matmuls: bool = False

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        return cls(
            num_layers=int(c["num_hidden_layers"]),
            d_model=int(c["hidden_size"]),
            num_heads=int(c["num_attention_heads"]),
            num_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            d_ff=int(c["intermediate_size"]),
            vocab_size=int(c["vocab_size"]),
            qk_norm=bool(c.get("qk_norm", False)),
            qkv_bias=bool(c.get("qkv_bias", False)),
            rope_theta=float(c["rope_theta"]),
            norm_eps=float(c["rms_norm_eps"]),
            dtype=str(c["torch_dtype"]),
        )


def control(a: Arch) -> Arch:
    return dataclasses.replace(a, int8_matmuls=True)


def program_config(a: Arch, name: str):
    """The program's ``ModelConfig`` for the sizes of ``a``."""
    from bench import system  # noqa: F401  (puts the program on the path)
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=name, family="dense", num_layers=a.num_layers,
        d_model=a.d_model, num_heads=a.num_heads,
        num_kv_heads=a.num_kv_heads, head_dim=a.head_dim, d_ff=a.d_ff,
        vocab_size=a.vocab_size, qk_norm=a.qk_norm, qkv_bias=a.qkv_bias,
        rope_theta=a.rope_theta, norm_eps=a.norm_eps, tie_embeddings=False,
        dtype=a.dtype,
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init_weights(key, a: Arch):
    """One member's weights from ``key``, in the program's layout.

    Matrices are normal with std fan_in^-0.5 (the embedding 0.02); norm
    scales are 1 + 0.1 N(0, 1) and biases 0.02 N(0, 1), so that every
    parameter the reference reads carries a value of its own."""
    dtype = jnp.dtype(a.dtype)
    D, H, KV, hd, F, V, L = (a.d_model, a.num_heads, a.num_kv_heads,
                             a.head_dim, a.d_ff, a.vocab_size, a.num_layers)
    ks = iter(jax.random.split(key, 32))

    def mat(shape, std=None):
        std = shape[-2] ** -0.5 if std is None else std
        return (jax.random.normal(next(ks), shape, jnp.float32) * std
                ).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    attn = {"wq": mat((L, D, H * hd)), "wk": mat((L, D, KV * hd)),
            "wv": mat((L, D, KV * hd)), "wo": mat((L, H * hd, D))}
    if a.qkv_bias:
        for name, n in (("bq", H), ("bk", KV), ("bv", KV)):
            attn[name] = mat((L, n * hd), std=0.02)
    if a.qk_norm:
        attn["q_norm"] = {"scale": scale((L, hd))}
        attn["k_norm"] = {"scale": scale((L, hd))}
    return {
        "embed": {"tok": mat((V, D), std=0.02)},
        "final_norm": {"scale": scale((D,))},
        "lm_head": {"w": mat((D, V))},
        "blocks": {
            "ln1": {"scale": scale((L, D))},
            "ln2": {"scale": scale((L, D))},
            "attn": attn,
            "mlp": {"w1": mat((L, D, F)), "w3": mat((L, D, F)),
                    "w2": mat((L, F, D))},
        },
    }


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


def _attention(q, k, v, q_pos, a: Arch):
    """Causal attention of queries at ``q_pos`` over keys 0..len(k)-1."""
    g = a.num_heads // a.num_kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / math.sqrt(
        a.head_dim)
    mask = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)


def attend(w, x, a: Arch, q_block: int):
    """A block's first half over a whole sequence x: (T, D): x plus its
    causal self-attention."""
    T = x.shape[0]
    at = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], a.norm_eps)
    q, k, v = _mm(h, at["wq"], a), _mm(h, at["wk"], a), _mm(h, at["wv"], a)
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    q = q.reshape(T, a.num_heads, a.head_dim)
    k = k.reshape(T, a.num_kv_heads, a.head_dim)
    v = v.reshape(T, a.num_kv_heads, a.head_dim)
    if a.qk_norm:
        q = rms_norm(q, at["q_norm"]["scale"], a.norm_eps)
        k = rms_norm(k, at["k_norm"]["scale"], a.norm_eps)
    pos = jnp.arange(T)
    q, k = rope(q, pos, a.rope_theta), rope(k, pos, a.rope_theta)
    outs = []
    for i0 in range(0, T, q_block):   # query blocks keep the scores small
        i1 = min(T, i0 + q_block)
        outs.append(_attention(q[i0:i1], k[:i1], v[:i1], pos[i0:i1], a))
    o = jnp.concatenate(outs, 0).reshape(T, -1)
    return x + _mm(o, at["wo"], a)


def _block(w, x, a: Arch, q_block: int):
    """One decoder block over a whole sequence x: (T, D)."""
    x = attend(w, x, a, q_block)
    h = rms_norm(x, w["ln2"]["scale"], a.norm_eps)
    m = w["mlp"]
    return x + _mm(jax.nn.silu(_mm(h, m["w1"], a)) * _mm(h, m["w3"], a),
                   m["w2"], a)


def hidden(params, tokens, a: Arch, q_block: int = 1024):
    """Final-normed hidden states (T, D) of one sequence."""
    x = params["embed"]["tok"][tokens]
    for i in range(a.num_layers):
        x = _block(_layer(params["blocks"], i), x, a, q_block)
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


def block_matmul_params(a: Arch) -> int:
    """Matmul weights of one decoder block (q, k, v, o and the SwiGLU)."""
    D, hd = a.d_model, a.head_dim
    attn = D * (a.num_heads + 2 * a.num_kv_heads) * hd + a.num_heads * hd * D
    return attn + 3 * D * a.d_ff


def head_params(a: Arch) -> int:
    return a.d_model * a.vocab_size


def attention_flops(a: Arch, pairs: int) -> int:
    """Forward attention of one layer over ``pairs`` (query, key) pairs:
    scores and the weighted sum of values, 2 * hd each, per head."""
    return 4 * a.num_heads * a.head_dim * pairs


def train_step(a: Arch, batch: int, seq: int) -> int:
    """Model FLOPs of one member's training step: forward and backward
    (3x the forward) of every matmul over all tokens, and of causal
    attention."""
    tokens = batch * seq
    matmul = 2 * (a.num_layers * block_matmul_params(a) + head_params(a))
    attn = a.num_layers * attention_flops(a, causal_pairs(0, seq)) * batch
    return 3 * (matmul * tokens + attn)


def prefill_chunk(a: Arch, start: int, length: int) -> int:
    """A prompt chunk of ``length`` tokens at position ``start``: every
    block for each token, causal attention over the context so far, and
    the head for the chunk's last token only."""
    matmul = 2 * a.num_layers * block_matmul_params(a) * length
    attn = a.num_layers * attention_flops(a, causal_pairs(start, length))
    return matmul + attn + 2 * head_params(a)


def decode_step(a: Arch, lengths: Iterable[int]) -> int:
    """One decode step of the active slots, whose contexts (the new token
    included) are ``lengths``."""
    lengths = list(lengths)
    per_token = 2 * (a.num_layers * block_matmul_params(a) + head_params(a))
    attn = a.num_layers * sum(attention_flops(a, n) for n in lengths)
    return per_token * len(lengths) + attn


def kv_page_bytes(a: Arch, page_size: int, itemsize: int = 2) -> int:
    """Bytes of one K or V page of one layer."""
    return page_size * a.num_kv_heads * a.head_dim * itemsize


def paged_attention_bytes(a: Arch, dmas: int, slots: int, page_size: int,
                          itemsize: int = 2) -> int:
    """Bytes of one paged-attention call (one layer): ``dmas`` K and V
    pages, each slot's query read and its output written once."""
    qo = 2 * slots * a.num_heads * a.head_dim * itemsize
    return 2 * dmas * kv_page_bytes(a, page_size, itemsize) + qo


def paged_attention_flops(a: Arch, lengths: Iterable[int]) -> int:
    return sum(attention_flops(a, n) for n in lengths)


def decode_kernel_flops(a: Arch, lengths: Iterable[int]) -> int:
    """The kernel's FLOPs in one decode step: one call a layer."""
    return a.num_layers * paged_attention_flops(a, lengths)


def decode_kernel_bytes(a: Arch, dmas: int, slots: int, page_size: int
                        ) -> int:
    """The kernel's bytes in one decode step: one call a layer."""
    return a.num_layers * paged_attention_bytes(a, dmas, slots, page_size)
