"""A test architecture that enters the benchmark as files: the decoder of
``dense_gqa`` with each block's SwiGLU replaced by a routed mixture of
SwiGLU experts, as the program's ``moe`` family runs it.

The router is a float32 matrix; each token takes the ``top_k`` experts of
the softmax of its router logits, weighted by their probabilities
renormalised over the chosen ones.  No shared expert.  The program runs
it with ``capacity_factor = E / top_k``, so that an expert's buffer holds
every token and none is dropped, and with no router loss, so that its
training loss is the cross entropy alone.  Its weight tree differs from
the dense one in ``blocks/mlp`` (``router`` and ``experts``), and its
``ModelConfig`` in the ``moe`` fields.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable

import jax
import jax.numpy as jnp

from bench.architectures import dense_gqa as G
from bench.flops import causal_pairs
from bench.reference import HIGHEST, rms_norm


@dataclasses.dataclass(frozen=True)
class Arch(G.Arch):
    num_experts: int = 0
    top_k: int = 0
    d_expert: int = 0

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        return cls(**dataclasses.asdict(G.Arch.from_config(c)),
                   num_experts=int(c["n_routed_experts"]),
                   top_k=int(c["num_experts_per_tok"]),
                   d_expert=int(c["moe_intermediate_size"]))


control = G.control


def program_config(a: Arch, name: str):
    return dataclasses.replace(
        G.program_config(a, name), family="moe", moe=True,
        n_routed_experts=a.num_experts, n_shared_experts=0, top_k=a.top_k,
        moe_d_ff=a.d_expert, capacity_factor=a.num_experts / a.top_k,
        router_aux_coef=0.0)


def init_weights(key, a: Arch):
    """The dense tree with ``blocks/mlp`` made anew: a float32 router and
    the experts' stacked SwiGLU matrices."""
    dtype = jnp.dtype(a.dtype)
    w = G.init_weights(key, a)
    L, D, E, F = a.num_layers, a.d_model, a.num_experts, a.d_expert
    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(key, 1), 4)

    def normal(k, shape, std):
        return jax.random.normal(k, shape, jnp.float32) * std

    w["blocks"]["mlp"] = {
        "router": normal(k1, (L, D, E), D ** -0.5),
        "experts": {"w1": normal(k2, (L, E, D, F), D ** -0.5).astype(dtype),
                    "w3": normal(k3, (L, E, D, F), D ** -0.5).astype(dtype),
                    "w2": normal(k4, (L, E, F, D), F ** -0.5).astype(dtype)},
    }
    return w


def _experts(m, h, a: Arch):
    """The routed experts over h: (T, D); every expert runs on every
    token and the gates, zero off a token's top_k, weight their sum."""
    probs = jax.nn.softmax(jnp.matmul(h, m["router"], precision=HIGHEST), -1)
    top_w, top_i = jax.lax.top_k(probs, a.top_k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)
    e = m["experts"]
    mm = jax.vmap(lambda w: G._mm(h, w, a), out_axes=1)
    out = jax.vmap(lambda x, w: G._mm(x, w, a), in_axes=(1, 0), out_axes=1)(
        jax.nn.silu(mm(e["w1"])) * mm(e["w3"]), e["w2"])
    return jnp.einsum("te,ted->td", gates, out, precision=HIGHEST)


def hidden(params, tokens, a: Arch, q_block: int = 1024):
    x = params["embed"]["tok"][tokens]
    for i in range(a.num_layers):
        w = G._layer(params["blocks"], i)
        x = G.attend(w, x, a, q_block)
        x = x + _experts(w["mlp"], rms_norm(x, w["ln2"]["scale"], a.norm_eps),
                         a)
    return rms_norm(x, params["final_norm"]["scale"], a.norm_eps)


def logits_at(params, tokens, rows, a: Arch):
    return G._mm(hidden(params, tokens, a)[rows], params["lm_head"]["w"], a)


def loss(params, tokens, a: Arch):
    def one(t):
        lg = G._mm(hidden(params, t, a, q_block=t.shape[0])[:-1],
                   params["lm_head"]["w"], a)
        lp = jax.nn.log_softmax(lg, -1)
        return -jnp.mean(jnp.take_along_axis(lp, t[1:, None], -1))
    return jnp.mean(jax.vmap(one)(tokens))


# -- counts: the dense ones with a block's matmuls those of its router and
# its top_k experts


def block_matmul_params(a: Arch) -> int:
    D, hd = a.d_model, a.head_dim
    attn = D * (a.num_heads + 2 * a.num_kv_heads) * hd + a.num_heads * hd * D
    return attn + D * a.num_experts + a.top_k * 3 * D * a.d_expert


def _per_token(a: Arch) -> int:
    return 2 * (a.num_layers * block_matmul_params(a) + G.head_params(a))


def train_step(a: Arch, batch: int, seq: int) -> int:
    attn = a.num_layers * G.attention_flops(a, causal_pairs(0, seq)) * batch
    return 3 * (_per_token(a) * batch * seq + attn)


def prefill_chunk(a: Arch, start: int, length: int) -> int:
    matmul = 2 * a.num_layers * block_matmul_params(a) * length
    attn = a.num_layers * G.attention_flops(a, causal_pairs(start, length))
    return matmul + attn + 2 * G.head_params(a)


def decode_step(a: Arch, lengths: Iterable[int]) -> int:
    lengths = list(lengths)
    attn = a.num_layers * sum(G.attention_flops(a, n) for n in lengths)
    return _per_token(a) * len(lengths) + attn


decode_kernel_flops = G.decode_kernel_flops
decode_kernel_bytes = G.decode_kernel_bytes
