"""The plain reference: weights from the seed, and a float32 dense decoder.

Nothing here imports the program under test.  The weights are made by
:func:`init_weights` from the seed, in the program's parameter layout (the
layout is the interface the program takes its weights in); the program
gets them in its configured dtype and the reference reads the same values
in float32.  The forward pass follows the published Qwen decoder: RMSNorm,
rotary embeddings (rotate-half, base ``rope_theta``), grouped-query causal
attention with optional q/k/v bias and per-head q/k RMSNorm, SwiGLU, an
untied head.  Every matmul runs at ``highest`` precision.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def init_weights(key, a: Arch, dtype=None):
    """One member's weights from ``key``, in the program's layout.

    Matrices are normal with std fan_in^-0.5 (the embedding 0.02); norm
    scales are 1 + 0.1 N(0, 1) and biases 0.02 N(0, 1), so that every
    parameter the reference reads carries a value of its own."""
    dtype = jnp.dtype(dtype or a.dtype)
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


def member_keys(seed: int, n: int):
    """The keys of ``n`` members drawn from ``seed`` (one per member)."""
    base = jax.random.fold_in(jax.random.key(0), seed % (1 << 31))
    base = jax.random.fold_in(base, seed >> 31)
    return [jax.random.fold_in(base, m) for m in range(n)]


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


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotate-half rotary embedding of x: (T, heads, hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def _block(w, x, a: Arch, q_block: int):
    """One decoder block over a whole sequence x: (T, D)."""
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
    x = x + _mm(o, at["wo"], a)
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


def to_f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


# ---------------------------------------------------------------------------
# the WASH shuffle (bucketed plan, paper Eq. 3 with shared randomness)
# ---------------------------------------------------------------------------


def _stratified(key, d: int, k: int):
    ko, ks = jax.random.split(key)
    i = jnp.arange(k)
    starts, ends = (i * d) // k, ((i + 1) * d) // k
    offs = jax.random.randint(ko, (k,), 0, jnp.iinfo(jnp.int32).max) % \
        jnp.maximum(ends - starts, 1)
    return jax.random.permutation(ks, (starts + offs).astype(jnp.int32))


def leaf_depths(params, num_layers: int) -> List:
    """Depth of each leaf (flatten order): the embedding 0, block i at
    i + 1 (stacked leaves get one depth per layer), the rest the last."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        top = path[0].key
        if top == "embed":
            out.append(0)
        elif top == "blocks":
            out.append(np.arange(1, num_layers + 1))
        else:
            out.append(num_layers + 1)
    return out


def wash_plan(key, member, num_layers: int, n: int, base_p: float):
    """Per-leaf (n, k) index plans of one WASH step: k coordinates per
    bucket s; bucket s moves member (m + s) mod n's value to member m.
    Probabilities fall linearly with depth to 0 at the last layer."""
    last = num_layers + 1
    plans = []
    for i, (leaf, depth) in enumerate(zip(jax.tree_util.tree_leaves(member),
                                          leaf_depths(member, num_layers))):
        k = jax.random.fold_in(key, i)
        if isinstance(depth, np.ndarray):
            d_rest = int(np.prod(leaf.shape[1:]))
            pieces = []
            for l, dl in enumerate(depth):
                k_l = int(round(base_p * (1.0 - dl / last) * d_rest))
                if k_l > 0:
                    pieces.append(_stratified(jax.random.fold_in(k, l), d_rest,
                                              min(k_l, d_rest)) + l * d_rest)
            if not pieces:
                plans.append(None)
                continue
            idx = jnp.concatenate(pieces)
            per = idx.shape[0] // n
            idx = jax.random.permutation(jax.random.fold_in(k, num_layers + 1),
                                         idx)
            plans.append(idx[:per * n].reshape(n, per) if per else None)
        else:
            p = base_p * (1.0 - depth / last)
            per = int(round(p * leaf.size)) // n if p > 0 else 0
            plans.append(_stratified(k, leaf.size, per * n).reshape(n, per)
                         if per else None)
    return plans


def wash_apply(members: List, plans) -> List:
    """Apply one step's plans to a list of member trees: in bucket s,
    member m takes member (m + s) mod n's value."""
    n = len(members)
    flat = [jax.tree_util.tree_leaves(m) for m in members]
    treedef = jax.tree_util.tree_structure(members[0])
    out = [list(f) for f in flat]
    for j, idx in enumerate(plans):
        if idx is None:
            continue
        vecs = [f[j].reshape(-1) for f in flat]
        new = list(vecs)
        for s in range(1, n):
            for m in range(n):
                new[m] = new[m].at[idx[s]].set(vecs[(m + s) % n][idx[s]])
        # buckets hold disjoint coordinates, so their order is immaterial
        for m in range(n):
            out[m][j] = new[m].reshape(flat[m][j].shape)
    return [jax.tree_util.tree_unflatten(treedef, o) for o in out]
