"""Shared neural-net layers: norms, rope, MLPs, GQA + MLA attention.

Everything is a pure function over explicit parameter dicts (no module
framework — flax is not available here and plain pytrees keep the WASH
shuffle logic trivial).  Compute-sensitive reductions run in float32.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Yarn

NEG_INF = -1e30


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 1 else 1
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def l2norm(x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float, scaling: Optional[Yarn] = None):
    """Inverse frequencies of the ``head_dim // 2`` rotated pairs; under
    YaRN ``scaling`` those of the pairs that turn fewer than ``beta_slow``
    times over the original context are divided by ``factor``, those that
    turn more than ``beta_fast`` times kept, with a linear ramp between."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is None or scaling.factor <= 1:
        return inv

    def pair(rotations):  # the pair index that turns ``rotations`` times
        return (head_dim * math.log(scaling.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(scaling.beta_fast)), 0)
    high = min(math.ceil(pair(scaling.beta_slow)), head_dim - 1)
    high = high + 0.001 if high == low else high
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x, positions, theta: float, scaling: Optional[Yarn] = None,
               interleaved: bool = False):
    """x: (..., T, H, hd); positions: (T,) or (..., T) absolute positions.

    Rotates pairs ``(i, i + hd/2)`` (rotate-half), or with ``interleaved``
    the pairs ``(2i, 2i + 1)``, returned de-interleaved (evens' results,
    then odds'), as DeepSeek-V2 does.  YaRN ``scaling`` changes the
    frequencies and scales cos and sin by ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta, scaling)  # (hd/2,)
    pos = positions.astype(jnp.float32)
    ang = pos[..., :, None] * inv[None, :]  # (..., T, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]  # (..., T, 1, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]
    if scaling is not None and scaling.factor > 1:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, (d_model, d_ff), dtype),
        "w3": dense_init(k2, (d_model, d_ff), dtype),
        "w2": dense_init(k3, (d_ff, d_model), dtype),
    }


def swiglu(p, x):
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    return h @ p["w2"]


def gelu_mlp_init(key, d_model, d_ff, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w1": dense_init(k1, (d_model, d_ff), dtype),
        "b1": jnp.zeros((d_ff,), dtype),
        "w2": dense_init(k2, (d_ff, d_model), dtype),
        "b2": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(p, x):
    return jax.nn.gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig):
    dtype = param_dtype(cfg)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.num_heads * hd), dtype),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, cfg.d_model), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _qkv(p, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, cfg.num_heads, hd)
    k = k.reshape(B, T, cfg.num_kv_heads, hd)
    v = v.reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.pos_kind == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def sdpa(q, k, v, mask, num_kv_heads: int):
    """q: (B,Tq,H,hd) k/v: (B,Tk,KV,hd); mask: (Tq,Tk) or (B,Tq,Tk) bool."""
    B, Tq, H, hd = q.shape
    kv = num_kv_heads
    g = H // kv
    qf = q.reshape(B, Tq, kv, g, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("btkgh,bskh->bkgts", qf, kf) / (hd ** 0.5)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w, vf)
    return out.reshape(B, Tq, H, hd).astype(q.dtype)


def sdpa_chunked(q, k, v, num_kv_heads: int, *, chunk: int, window=None,
                 bidirectional: bool = False):
    """Online-softmax attention over kv chunks — never materializes SxS.

    Pure-jnp flash-style formulation (lax.scan over kv chunks with running
    max/sum), so it lowers through XLA on any backend and is differentiable;
    the Pallas kernel (repro.kernels.flash_attention) is the TPU-tiled
    version of the same schedule.  Used when cfg.attn_impl == "chunked".
    """
    B, Tq, H, hd = q.shape
    S = k.shape[1]
    kv = num_kv_heads
    g = H // kv
    chunk = min(chunk, S)
    assert S % chunk == 0, "pad S to a chunk multiple"
    qf = q.reshape(B, Tq, kv, g, hd).astype(jnp.float32) * (hd ** -0.5)
    qpos = jnp.arange(Tq)

    def body(carry, inp):
        acc, m_prev, l_prev = carry
        j, k_c, v_c = inp  # chunk idx, (B,chunk,kv,hd) x2
        scores = jnp.einsum("btkgh,bskh->bkgts", qf, k_c.astype(jnp.float32))
        kpos = j * chunk + jnp.arange(chunk)
        mask = jnp.ones((Tq, chunk), bool)
        if not bidirectional:
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgts,bskh->bkgth", p, v_c.astype(jnp.float32)
        )
        return (acc_new, m_new, l_new), None

    n_chunks = S // chunk
    k_c = k.reshape(B, n_chunks, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    v_c = v.reshape(B, n_chunks, chunk, kv, hd).transpose(1, 0, 2, 3, 4)
    acc0 = jnp.zeros((B, kv, g, Tq, hd), jnp.float32)
    m0 = jnp.full((B, kv, g, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, kv, g, Tq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(n_chunks), k_c, v_c)
    )
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, hd).astype(q.dtype)


def sdpa_banded(q, k, v, num_kv_heads: int, *, window: int):
    """Sliding-window attention in O(S·2W): each W-sized query block attends
    only to its own and the previous key block (relative mask inside).

    The naive/chunked paths still *compute* S×S (masked) scores; for SWA
    archs (hymba, the long_500k dense variants) this banded form is the
    memory-roofline fix — score traffic drops by S/(2W).
    """
    B, S, H, hd = q.shape
    kv = num_kv_heads
    g = H // kv
    W = window
    assert S % W == 0, "pad S to a window multiple"
    nb = S // W
    qf = (q.reshape(B, nb, W, kv, g, hd).astype(jnp.float32)) * (hd ** -0.5)
    kb = k.reshape(B, nb, W, kv, hd).astype(jnp.float32)
    vb = v.reshape(B, nb, W, kv, hd).astype(jnp.float32)
    # previous block (zeros before block 0)
    zeros = jnp.zeros_like(kb[:, :1])
    k2 = jnp.concatenate([jnp.concatenate([zeros, kb[:, :-1]], 1), kb], axis=2)  # (B,nb,2W,kv,hd)
    v2 = jnp.concatenate([jnp.concatenate([zeros, vb[:, :-1]], 1), vb], axis=2)
    scores = jnp.einsum("bntkgh,bnskh->bnkgts", qf, k2)  # (B,nb,kv,g,W,2W)
    qpos = jnp.arange(W)[:, None]
    kpos = jnp.arange(2 * W)[None, :] - W
    rel = qpos - kpos  # how far behind the key is
    mask = (rel >= 0) & (rel < W)  # causal + window
    first = jnp.arange(2 * W)[None, :] >= W  # block 0 has no previous block
    m_all = jnp.broadcast_to(mask[None], (nb, W, 2 * W))
    m_all = m_all.at[0].set(mask & first)
    scores = jnp.where(m_all[None, :, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnkgts,bnskh->bntkgh", w, v2)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def causal_mask(T: int, window: Optional[int] = None):
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    m = j <= i
    if window is not None:
        m = m & (j > i - window)
    return m


def gqa_train(p, cfg: ModelConfig, x, bidirectional: bool = False):
    B, T, _ = x.shape
    positions = jnp.arange(T)
    q, k, v = _qkv(p, cfg, x, positions)
    if cfg.attn_impl == "chunked" and not bidirectional and cfg.window and T % cfg.window == 0 and T > cfg.window:
        out = sdpa_banded(q, k, v, cfg.num_kv_heads, window=cfg.window)
    elif cfg.attn_impl == "chunked":
        out = sdpa_chunked(q, k, v, cfg.num_kv_heads, chunk=min(cfg.attn_chunk, T),
                           window=cfg.window, bidirectional=bidirectional)
    else:
        if bidirectional:
            mask = jnp.ones((T, T), bool)
        else:
            mask = causal_mask(T, cfg.window)
        out = sdpa(q, k, v, mask, cfg.num_kv_heads)
    return out.reshape(B, T, -1) @ p["wo"]


# -- KV cache -------------------------------------------------------------


def gqa_cache_init(cfg: ModelConfig, batch: int, capacity: int, num_layers: int):
    hd = cfg.resolved_head_dim
    dtype = param_dtype(cfg)
    return {
        "k": jnp.zeros((num_layers, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((num_layers, batch, capacity, cfg.num_kv_heads, hd), dtype),
        "pos_ids": jnp.full((num_layers, capacity), -1, jnp.int32),
    }


def gqa_prefill(p, cfg: ModelConfig, x, cache_l):
    """Full-sequence attention that also fills this layer's cache slice."""
    B, T, _ = x.shape
    positions = jnp.arange(T)
    q, k, v = _qkv(p, cfg, x, positions)
    cap = cache_l["k"].shape[1]
    # prefill writes the last `cap` tokens; ring layout slot = pos % cap so
    # a later decode step can keep appending at (pos % cap).
    start = max(T - cap, 0)
    if cap <= T:
        shift = start % cap
        cache_l = {
            "k": jnp.roll(k[:, start:], shift, axis=1).astype(cache_l["k"].dtype),
            "v": jnp.roll(v[:, start:], shift, axis=1).astype(cache_l["v"].dtype),
            "pos_ids": jnp.roll(jnp.arange(start, T, dtype=jnp.int32), shift),
        }
    else:
        cache_l = {
            "k": jax.lax.dynamic_update_slice(
                cache_l["k"], k.astype(cache_l["k"].dtype), (0, 0, 0, 0)
            ),
            "v": jax.lax.dynamic_update_slice(
                cache_l["v"], v.astype(cache_l["v"].dtype), (0, 0, 0, 0)
            ),
            "pos_ids": jax.lax.dynamic_update_slice(
                cache_l["pos_ids"], jnp.arange(T, dtype=jnp.int32), (0,)
            ),
        }
    if cfg.attn_impl == "chunked" and cfg.window and T % cfg.window == 0 and T > cfg.window:
        out = sdpa_banded(q, k, v, cfg.num_kv_heads, window=cfg.window)
    elif cfg.attn_impl == "chunked":
        out = sdpa_chunked(q, k, v, cfg.num_kv_heads,
                           chunk=min(cfg.attn_chunk, T), window=cfg.window)
    else:
        mask = causal_mask(T, cfg.window)
        out = sdpa(q, k, v, mask, cfg.num_kv_heads)
    return out.reshape(B, T, -1) @ p["wo"], cache_l


def gqa_decode(p, cfg: ModelConfig, x, cache_l, pos):
    """One-token decode against this layer's cache slice.

    ``pos`` is the absolute position of the new token.  The cache is a ring
    of size ``capacity``: full-attention archs use capacity == seq_len;
    sliding-window archs use capacity == window, giving O(window) decode
    regardless of logical context length (long_500k path).
    """
    B, T, _ = x.shape
    assert T == 1
    q, k, v = _qkv(p, cfg, x, jnp.asarray(pos)[None])
    cap = cache_l["k"].shape[1]
    slot = jnp.asarray(pos, jnp.int32) % cap
    ck = jax.lax.dynamic_update_index_in_dim(cache_l["k"], k[:, 0].astype(cache_l["k"].dtype), slot, 1)
    cv = jax.lax.dynamic_update_index_in_dim(cache_l["v"], v[:, 0].astype(cache_l["v"].dtype), slot, 1)
    cpos = jax.lax.dynamic_update_index_in_dim(
        cache_l["pos_ids"], jnp.asarray(pos, jnp.int32), slot, 0
    )
    cache_l = {"k": ck, "v": cv, "pos_ids": cpos}
    valid = cpos >= 0
    if cfg.window is not None:
        valid = valid & (cpos > pos - cfg.window)
    out = sdpa(q, ck, cv, valid[None, :], cfg.num_kv_heads)  # (Tq=1, cap) mask
    return out.reshape(B, 1, -1) @ p["wo"], cache_l


# -- paged KV cache (continuous-batching serving) ---------------------------

#: supported storage dtypes for the paged pools: None = the model's param
#: dtype (the bitwise-exact path); "int8" = per-page symmetric quantization
#: with a float32 scale per (layer, page), halving pool HBM
KV_DTYPES = (None, "int8")

#: adaptive page scales start here and only ever grow (monotone), so a
#: page's already-written rows are rescaled at most once per scale bump
KV_SCALE_FLOOR = 1e-8

#: page 0 (the runtime's scratch page) keeps this scale FOREVER: masked
#: garbage writes from inactive slots must never adapt quantization state
KV_SCRATCH_SCALE = 1.0


def paged_pools_init(cfg: ModelConfig, num_pages: int, page_size: int,
                     num_layers: int, kv_dtype: str = None):
    """Block-pool KV cache: ``num_pages`` shared fixed-size pages per layer.

    The pools are one pytree, which the serving programs thread whole:
    ``{"k", "v"}`` for grouped-query attention, ``{"latent"}`` for MLA.

    A GQA pool is laid out ``(num_layers, num_pages, page_size * KV,
    hd)``: row ``offset * KV + h`` of a page holds kv head ``h`` at
    in-page position ``offset``.  That is the ``(page_size * KV, hd)`` tile
    the paged kernel reads, so the pool needs no relayout on its way in,
    and ``pool.reshape(L * P, page_size, KV, hd)`` is a free view of every
    layer's pages at once (:func:`paged_kernel_view`).  The MLA pool
    holds, for each position, one row ``[c | k_pe]`` of ``W =
    kv_lora_rank + qk_rope_dim`` elements (the normalised latent and the
    roped rope key, read by every head), two positions a row:
    ``(num_layers, num_pages, page_size // 2, 2 * W)``, whose elements lie
    in the order of ``(..., page_size, W)``.  At DeepSeek-V2's W = 576
    (4.5 lanes) the chip's default layout of a W-wide minor dimension puts
    the page axis minor, and every program would relay the pool out; 2W =
    1152 is whole lanes (:func:`latent_pool_rows`).  The per-slot
    view is a **page table** of pool indices, not a contiguous slice, so
    slots with different context lengths share one allocation and common
    prompt prefixes can share pages (``repro.serving.batching`` owns the
    table/refcount bookkeeping).  Page 0 is reserved by the runtime as a
    scratch page for inactive slots.

    The serving programs donate the pools and update them **in place**:
    the layer loop carries the whole pools, a step scatters only its new
    rows into ``[layer, page, offset * KV : (offset + 1) * KV]``, and the
    attend reads the view above with the page table offset by
    ``layer * num_pages``; nothing slices out, relayouts or writes back a
    layer's pool.

    ``kv_dtype=None`` stores pages in the model's param dtype (bitwise
    path).  ``kv_dtype="int8"`` (GQA only) stores each pool as
    ``{"q": int8 (L, P, page_size * KV, hd), "scale": f32 (L, P)}`` — one
    symmetric scale per (layer, page), written by
    :func:`paged_store_rows` / :func:`paged_store_chunk` and applied at
    read time inside both paged attends.  Page 0's scale is pinned to
    :data:`KV_SCRATCH_SCALE` and never adapts."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype={kv_dtype!r}; expected one of {KV_DTYPES}")
    if cfg.mla:
        if kv_dtype is not None:
            raise NotImplementedError(
                f"kv_dtype={kv_dtype!r}: the MLA latent pool has no "
                "quantized layout")
        if page_size % 2:
            raise ValueError(f"page_size={page_size}: the latent pool holds "
                             "two positions a row")
        W = cfg.kv_lora_rank + cfg.qk_rope_dim
        return {"latent": jnp.zeros(
            (num_layers, num_pages, page_size // 2, 2 * W), param_dtype(cfg))}
    hd = cfg.resolved_head_dim
    shape = (num_layers, num_pages, page_size * cfg.num_kv_heads, hd)
    if kv_dtype is None:
        dtype = param_dtype(cfg)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    scale = jnp.full((num_layers, num_pages), KV_SCALE_FLOOR, jnp.float32)
    scale = scale.at[:, 0].set(KV_SCRATCH_SCALE)
    pool = {"q": jnp.zeros(shape, jnp.int8), "scale": scale}
    return {"k": pool, "v": jax.tree_util.tree_map(lambda x: x, pool)}


def kv_quantize(x, scale):
    """Symmetric int8 quantization of ``x`` under per-page ``scale``
    (broadcast against x's leading axes)."""
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def kv_dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def kv_page_scale(x, floor: float = None):
    """The smallest symmetric-int8 scale covering ``x`` (amax / 127)."""
    floor = KV_SCALE_FLOOR if floor is None else floor
    return jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, floor)


def _window_scatter(pool, idx, values):
    """Write ``values[n]`` into the window of ``pool`` that starts at
    ``idx[n]``: ``idx`` indexes the pool's leading ``k = idx.shape[-1]``
    axes, the first ``k - 1`` of which the window drops, so ``values[n]``
    spans the rest of the pool's axes from ``idx[n, k - 1]`` on."""
    k = idx.shape[-1]
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, pool.ndim - k + 2)),
        inserted_window_dims=tuple(range(k - 1)),
        scatter_dims_to_operand_dims=tuple(range(k)))
    return jax.lax.scatter(pool, idx, values.astype(pool.dtype), dnums,
                           mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


@jax.custom_batching.custom_vmap
def _pool_scatter(pool, idx, values):
    return _window_scatter(pool, idx, values)


@_pool_scatter.def_vmap
def _pool_scatter_vmap(axis_size, in_batched, pool, idx, values):
    # Under vmap (ensemble serving vmaps members over stacked pools) the
    # member becomes one more scatter index.  JAX's own rule would make it
    # an axis of each update's window, and the TPU compiler then lays the
    # whole pool out member-minor for the scatter and copies it back for
    # the kernel every layer.
    pool, idx, values = (
        x if b else jnp.broadcast_to(x, (axis_size,) + x.shape)
        for x, b in zip((pool, idx, values), in_batched))
    n = idx.shape[1]
    member = jnp.broadcast_to(
        jnp.arange(axis_size, dtype=idx.dtype)[:, None, None],
        (axis_size, n, 1))
    idx = jnp.concatenate([member, idx], axis=-1).reshape(axis_size * n, -1)
    values = values.reshape((axis_size * n,) + values.shape[2:])
    return _window_scatter(pool, idx, values), True


def _pool_write(pool, layer, pages, starts, values):
    """Write ``values[n]`` (rows, hd) into ``pool[layer, pages[n],
    starts[n] : starts[n] + rows]`` in place: a scatter of those windows
    alone, whether or not the pool is vmapped over members."""
    n = values.shape[0]
    idx = jnp.stack([jnp.broadcast_to(layer, (n,)), pages,
                     jnp.broadcast_to(starts, (n,))], axis=-1)
    return _pool_scatter(pool, idx.astype(jnp.int32), values)


def paged_store_rows(pool, layer, page_idx, offset, rows):
    """Write one (KV, hd) row per batch entry into layer ``layer`` of the
    whole ``pool`` at ``(page_idx[b], offset[b])`` — the decode-step
    scatter.  Only the ``B`` windows ``[layer, page_idx[b],
    offset[b] * KV : (offset[b] + 1) * KV]`` are written; the rest of the
    pool keeps its buffer and its bits.

    For plain pools this is the raw scatter.  For int8 pools the
    written pages' scales grow monotonically to cover the new rows
    (``max(old, amax(row)/127)``): untouched pages keep their bits, and a
    page whose scale does not change keeps its already-written rows
    bit-identical (the rescale ratio is exactly 1.0).  Page 0 (scratch)
    never adapts — its scale stays :data:`KV_SCRATCH_SCALE`.

    ``page_idx`` MAY contain duplicates (the speculative verify step
    scatters several rows of one slot — often one page — in a single
    call): scales merge through a scatter-max, every duplicate gathers
    the same pre-step page bits and rescales them identically, and the
    new rows land via a per-``(page, offset)`` scatter whose index pairs
    are distinct for live rows."""
    kv_heads = rows.shape[1]
    starts = offset * kv_heads
    if not isinstance(pool, dict):
        return _pool_write(pool, layer, page_idx, starts, rows)
    q, scale = pool["q"], pool["scale"]
    rows = rows.astype(jnp.float32)                       # (B, KV, hd)
    row_amax = jnp.max(jnp.abs(rows), axis=(1, 2))        # (B,)
    s_old = scale[layer]                                  # (P,)
    s_new = s_old.at[page_idx].max(row_amax / 127.0)      # dup-safe
    s_new = s_new.at[0].set(KV_SCRATCH_SCALE)
    # rescale the touched pages' existing bits; duplicates gather the same
    # old page and the same (s_old/s_new) ratio, so their scatter-back
    # writes are identical and any winner is correct
    ratio = (s_old / s_new)[page_idx]                     # (B,)
    pages = jnp.round(q[layer, page_idx].astype(jnp.float32)
                      * ratio[:, None, None])
    pages = jnp.clip(pages, -127, 127).astype(jnp.int8)
    q = _pool_write(q, layer, page_idx, 0, pages)
    qrows = jnp.clip(jnp.round(rows / s_new[page_idx][:, None, None]),
                     -127, 127).astype(jnp.int8)
    return {"q": _pool_write(q, layer, page_idx, starts, qrows),
            "scale": scale.at[layer].set(s_new)}


def _chunk_window(page_table, pos, page_size: int):
    """The pool pages a chunk of rows at positions ``pos`` touches, as a
    static window of ``T // page_size + 2`` logical pages from the page of
    ``pos[0]``: ``(pids, local, offs)`` — each window entry's pool page
    (entries past the chunk's last page redirected to the scratch page 0,
    so no live page is ever gathered or written without rows), and each
    row's window entry and in-page offset."""
    T = pos.shape[0]
    max_pages = page_table.shape[0]
    n_w = T // page_size + 2
    first = pos[0] // page_size
    window = first + jnp.arange(n_w)                      # logical pages
    touched = window <= pos[T - 1] // page_size
    pids = jnp.where(touched,
                     page_table[jnp.minimum(window, max_pages - 1)], 0)
    return pids, pos // page_size - first, pos % page_size


def paged_store_chunk(pool, layer, page_table, positions, rows):
    """Write a contiguous chunk of rows for ONE slot into layer ``layer``
    of the whole ``pool`` — the prefill scatter.  Only the chunk's rows
    ``[layer, page_table[pos // page_size], (pos % page_size) * KV + h]``
    change.

    ``positions`` are the rows' absolute positions.  The chunk's pages
    (:func:`_chunk_window`) are gathered, given their new rows, and
    written back whole: one window of ``page_size * KV`` rows a page, not
    one a row, since the chip writes a scatter's windows one at a time.
    The rest of each page goes back bit for bit.  Same quantization
    discipline as :func:`paged_store_rows`."""
    pos = positions.astype(jnp.int32)
    T, kv_heads, hd = rows.shape
    quantized = isinstance(pool, dict)
    q = pool["q"] if quantized else pool
    page_size = q.shape[2] // kv_heads
    pids, local, offs = _chunk_window(page_table, pos, page_size)
    n_w = pids.shape[0]
    pages = q[layer, pids]                                # (n_w, ps*KV, hd)
    if not quantized:
        pages = pages.reshape(n_w, page_size, kv_heads, hd).at[
            local, offs].set(rows.astype(q.dtype))
        return _pool_write(q, layer, pids, 0, pages.reshape(n_w, -1, hd))
    scale = pool["scale"]
    rows = rows.astype(jnp.float32)                       # (T, KV, hd)
    row_amax = jnp.max(jnp.abs(rows), axis=(1, 2))        # (T,)
    page_amax = jnp.zeros((n_w,), jnp.float32).at[local].max(row_amax)
    s_old = scale[layer, pids]
    s_new = jnp.maximum(s_old, page_amax / 127.0)
    s_new = jnp.where(pids == 0, KV_SCRATCH_SCALE, s_new)
    pages = jnp.round(pages.astype(jnp.float32)
                      * (s_old / s_new)[:, None, None])
    pages = pages.reshape(n_w, page_size, kv_heads, hd).at[local, offs].set(
        jnp.round(rows / s_new[local][:, None, None]))
    pages = jnp.clip(pages, -127, 127).astype(jnp.int8)
    return {"q": _pool_write(q, layer, pids, 0, pages.reshape(n_w, -1, hd)),
            "scale": scale.at[layer, pids].set(s_new)}


def paged_kernel_view(pool, layer, page_table, kv_heads: int):
    """What the paged attends read of layer ``layer``: the whole pool
    viewed as ``(L * P, page_size, KV, hd)`` (a free reshape) with
    ``page_table`` offset to that layer's pages, and for int8 pools the
    scales flattened to ``(L * P,)``.  Returns ``(pages, table, scale)``,
    ``scale`` None for plain pools."""
    q = pool["q"] if isinstance(pool, dict) else pool
    n_layers, P, R, hd = q.shape
    view = q.reshape(n_layers * P, R // kv_heads, kv_heads, hd)
    scale = (pool["scale"].reshape(n_layers * P) if isinstance(pool, dict)
             else None)
    return view, page_table + layer * P, scale


def gqa_decode_paged(p, cfg: ModelConfig, x, pools, layer,
                     page_table, positions, use_pallas: bool):
    """One-token decode for a batch of slots against the paged pool.

      x          : (B, 1, D) — one new token per slot
      pools      : {"k","v"}: (L, P, page_size * KV, hd) — the WHOLE pools
                   (:func:`paged_pools_init`), updated in place
      layer      : int32 scalar — this layer's index into the pools
      page_table : (B, max_pages) int32
      positions  : (B,) int32 — absolute write position of each new token
                   (its page is ``page_table[b, pos // page_size]``)
      use_pallas : route the attend through the fused Pallas kernel
                   (``kernels.paged_attention``) instead of the jnp
                   gather+attend oracle (``kernels.ref``)

    Every slot's new K/V lands in a page that slot owns exclusively (the
    runtime never hands a shared prefix page out as a write target), so
    the scatter below cannot collide across slots.  The attend reads
    :func:`paged_kernel_view` of the pools.  int8 pools (``{"q","scale"}``
    dicts — see :func:`paged_pools_init`) quantize the write and
    dequantize inside the attend.  Returns ``(out (B,1,D), pools)``.
    """
    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.ref import paged_attention_ref

    B, T, _ = x.shape
    assert T == 1
    q, k, v = _qkv(p, cfg, x, positions[:, None])
    k_pool, v_pool = pools["k"], pools["v"]
    KV = cfg.num_kv_heads
    page_size = (k_pool["q"] if isinstance(k_pool, dict)
                 else k_pool).shape[2] // KV
    pos = positions.astype(jnp.int32)
    page_idx = page_table[jnp.arange(B), pos // page_size]  # (B,)
    offset = pos % page_size
    with jax.named_scope("kv_write"):
        k_pool = paged_store_rows(k_pool, layer, page_idx, offset, k[:, 0])
        v_pool = paged_store_rows(v_pool, layer, page_idx, offset, v[:, 0])
    lengths = pos + 1  # context = everything written so far incl. this token
    attend = paged_attention_pallas if use_pallas else paged_attention_ref
    with jax.named_scope("paged_attention"):
        kp, table, ks = paged_kernel_view(k_pool, layer, page_table, KV)
        vp, _, vs = paged_kernel_view(v_pool, layer, page_table, KV)
        if ks is not None:
            out = attend(q[:, 0], kp, vp, table, lengths, k_scale=ks,
                         v_scale=vs)
        else:
            out = attend(q[:, 0], kp, vp, table, lengths)
    return out.reshape(B, 1, -1) @ p["wo"], {"k": k_pool, "v": v_pool}


def gqa_prefill_paged(p, cfg: ModelConfig, x, pools, layer,
                      page_table, positions):
    """Chunk/suffix prefill for ONE slot against the paged pool.

      x          : (1, T, D) — hidden states of a contiguous prompt chunk
      pools      : {"k","v"}: (L, P, page_size * KV, hd) — the WHOLE
                   pools, updated in place
      layer      : int32 scalar — this layer's index into the pools
      page_table : (max_pages,) int32 — the slot's pages, prompt order
      positions  : (T,) int32 — absolute positions pos0 .. pos0+T-1
                   (traced, so one compile serves every chunk offset)

    Writes the chunk's K/V into the slot's pages (all write targets are
    slot-owned — cached prefix pages sit strictly below ``positions[0]``
    and are never written), then attends over the table-gathered context
    ``pool[layer, page_table]`` (the slot's pages, not the pool) under
    the causal mask ``j <= position``.  Rows are bitwise-identical
    to the same rows of the whole-prompt :func:`gqa_prefill`: q/k/v are
    per-position ops, the gathered context lists positions in order, and
    the masked tail contributes exact zeros to the softmax and the value
    sum — same argument (and same test evidence) as
    :func:`gqa_decode_paged` vs :func:`gqa_decode`.
    """
    B, T, _ = x.shape
    assert B == 1
    q, k, v = _qkv(p, cfg, x, positions)
    k_pool, v_pool = pools["k"], pools["v"]
    pos = positions.astype(jnp.int32)
    # in-chunk positions are distinct, so the store never scatter-dups
    with jax.named_scope("kv_write"):
        k_pool = paged_store_chunk(k_pool, layer, page_table, pos, k[0])
        v_pool = paged_store_chunk(v_pool, layer, page_table, pos, v[0])

    def context(pool):
        if isinstance(pool, dict):
            c = kv_dequantize(pool["q"][layer, page_table],
                              pool["scale"][layer, page_table][:, None, None])
        else:
            c = pool[layer, page_table]
        return c.reshape(1, -1, cfg.num_kv_heads, k.shape[-1])

    kc, vc = context(k_pool), context(v_pool)
    ctx = kc.shape[1]
    mask = jnp.arange(ctx)[None, :] <= pos[:, None]  # (T, ctx)
    out = sdpa(q, kc, vc, mask, cfg.num_kv_heads)
    return out.reshape(B, T, -1) @ p["wo"], {"k": k_pool, "v": v_pool}


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)
# ---------------------------------------------------------------------------


def xattn_init(key, cfg: ModelConfig):
    dtype = param_dtype(cfg)
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.num_heads * hd), dtype),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, cfg.d_model), dtype),
    }


def xattn(p, cfg: ModelConfig, x, kv_feats):
    """kv_feats: encoder output (B, S_enc, D) — no rope, full visibility."""
    B, T, _ = x.shape
    S = kv_feats.shape[1]
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, T, cfg.num_heads, hd)
    k = (kv_feats @ p["wk"]).reshape(B, S, cfg.num_kv_heads, hd)
    v = (kv_feats @ p["wv"]).reshape(B, S, cfg.num_kv_heads, hd)
    mask = jnp.ones((T, S), bool)
    out = sdpa(q, k, v, mask, cfg.num_kv_heads)
    return out.reshape(B, T, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2, arXiv:2405.04434)
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig):
    """``w_dkv`` and ``w_krope`` together are DeepSeek-V2's ``kv_a_proj``
    (the latent ``c`` and the shared rope key), ``kv_norm`` its
    ``kv_a_layernorm``, ``w_uk`` and ``w_uv`` its ``kv_b_proj``."""
    dtype = param_dtype(cfg)
    H = cfg.num_heads
    ks = jax.random.split(key, 6)
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": dense_init(ks[0], (cfg.d_model, H * qd), dtype),
        "w_dkv": dense_init(ks[1], (cfg.d_model, cfg.kv_lora_rank), dtype),
        "w_krope": dense_init(ks[2], (cfg.d_model, cfg.qk_rope_dim), dtype),
        "kv_norm": rmsnorm_init(cfg.kv_lora_rank, dtype),
        "w_uk": dense_init(ks[3], (cfg.kv_lora_rank, H * cfg.qk_nope_dim), dtype),
        "w_uv": dense_init(ks[4], (cfg.kv_lora_rank, H * cfg.v_head_dim), dtype),
        "wo": dense_init(ks[5], (H * cfg.v_head_dim, cfg.d_model), dtype),
    }


def mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale: ``(qk_nope + qk_rope)^-0.5``, times
    ``yarn_mscale(factor, mscale_all_dim)^2`` under YaRN."""
    s = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _mla_rope(cfg: ModelConfig, x, positions):
    return apply_rope(x, positions, cfg.rope_theta, cfg.rope_scaling,
                      interleaved=True)


def _mla_q(p, cfg: ModelConfig, x, positions):
    B, T, _ = x.shape
    H = cfg.num_heads
    q = (x @ p["wq"]).reshape(B, T, H, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    return q_nope, _mla_rope(cfg, q_rope, positions)


def _mla_latent(p, cfg: ModelConfig, x, positions):
    """What the cache holds of x: (B, T, D): the normalised latent ``c``
    (B, T, r) and the roped rope key (B, T, qk_rope), one for all heads."""
    c = rmsnorm(p["kv_norm"], x @ p["w_dkv"], cfg.norm_eps)
    krope = _mla_rope(cfg, (x @ p["w_krope"])[:, :, None, :], positions)
    return c, krope[:, :, 0]


def _mla_absorb(p, cfg: ModelConfig, q_nope, q_rope):
    """Queries against the latent cache, (..., H, r + qk_rope) float32:
    ``q_nope`` absorbed through ``w_uk`` (``q_nope . (c w_uk) =
    (q_nope w_uk^T) . c``), then the rope query."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    wk = p["w_uk"].reshape(r, H, cfg.qk_nope_dim).astype(jnp.float32)
    q_abs = jnp.einsum("...hd,rhd->...hr", q_nope.astype(jnp.float32), wk)
    return jnp.concatenate([q_abs, q_rope.astype(jnp.float32)], axis=-1)


def _mla_out(p, cfg: ModelConfig, lat, dtype):
    """The latent attention output lat: (..., H, r) expanded through
    ``w_uv`` and projected: (..., D)."""
    H, r = cfg.num_heads, cfg.kv_lora_rank
    wv = p["w_uv"].reshape(r, H, cfg.v_head_dim).astype(jnp.float32)
    out = jnp.einsum("...hr,rhd->...hd", lat.astype(jnp.float32), wv)
    return out.reshape(out.shape[:-2] + (-1,)).astype(dtype) @ p["wo"]


def mla_train(p, cfg: ModelConfig, x):
    """Training/prefill form: latents expanded to per-head K/V."""
    B, T, _ = x.shape
    H = cfg.num_heads
    positions = jnp.arange(T)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, krope = _mla_latent(p, cfg, x, positions)
    k_nope = (ckv @ p["w_uk"]).reshape(B, T, H, cfg.qk_nope_dim)
    v = (ckv @ p["w_uv"]).reshape(B, T, H, cfg.v_head_dim)
    scores = (
        jnp.einsum("bthd,bshd->bhts", q_nope.astype(jnp.float32), k_nope.astype(jnp.float32))
        + jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32), krope.astype(jnp.float32))
    ) * mla_scale(cfg)
    mask = causal_mask(T)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", w, v.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, T, -1) @ p["wo"]


def mla_cache_init(cfg: ModelConfig, batch: int, capacity: int, num_layers: int):
    dtype = param_dtype(cfg)
    return {
        "ckv": jnp.zeros((num_layers, batch, capacity, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((num_layers, batch, capacity, cfg.qk_rope_dim), dtype),
        "pos_ids": jnp.full((num_layers, capacity), -1, jnp.int32),
    }


def mla_prefill(p, cfg: ModelConfig, x, cache_l):
    B, T, _ = x.shape
    out = mla_train(p, cfg, x)
    ckv, krope = _mla_latent(p, cfg, x, jnp.arange(T))
    cache_l = {
        "ckv": cache_l["ckv"].at[:, :T].set(ckv.astype(cache_l["ckv"].dtype)),
        "krope": cache_l["krope"].at[:, :T].set(krope.astype(cache_l["krope"].dtype)),
        "pos_ids": cache_l["pos_ids"].at[:T].set(jnp.arange(T, dtype=jnp.int32)),
    }
    return out, cache_l


def mla_decode(p, cfg: ModelConfig, x, cache_l, pos):
    """Absorbed decode: scores/values computed against the *latent* cache.

    q_nope is absorbed through w_uk (q' = q_nope @ w_uk per head) and the
    attention output is read in latent space then expanded through w_uv —
    the memory-bandwidth-optimal MLA decode form.
    """
    B, T, _ = x.shape
    assert T == 1
    r = cfg.kv_lora_rank
    q_nope, q_rope = _mla_q(p, cfg, x, jnp.asarray(pos)[None])
    ckv_t, krope_t = _mla_latent(p, cfg, x, jnp.asarray(pos)[None])
    cap = cache_l["ckv"].shape[1]
    slot = jnp.asarray(pos, jnp.int32) % cap
    ckv = jax.lax.dynamic_update_index_in_dim(
        cache_l["ckv"], ckv_t[:, 0].astype(cache_l["ckv"].dtype), slot, 1
    )
    krope = jax.lax.dynamic_update_index_in_dim(
        cache_l["krope"], krope_t[:, 0].astype(cache_l["krope"].dtype), slot, 1
    )
    cpos = jax.lax.dynamic_update_index_in_dim(
        cache_l["pos_ids"], jnp.asarray(pos, jnp.int32), slot, 0
    )
    cache_l = {"ckv": ckv, "krope": krope, "pos_ids": cpos}

    q = _mla_absorb(p, cfg, q_nope[:, 0], q_rope[:, 0])        # (B,H,r+rope)
    keys = jnp.concatenate([ckv, krope], -1).astype(jnp.float32)  # (B,S,r+rope)
    scores = jnp.einsum("bhd,bsd->bhs", q, keys) * mla_scale(cfg)
    scores = jnp.where((cpos >= 0)[None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    lat = jnp.einsum("bhs,bsr->bhr", w, keys[..., :r])
    return _mla_out(p, cfg, lat, x.dtype)[:, None], cache_l


# -- MLA over the paged latent pool ------------------------------------------


def latent_pool_rows(pool, layer, page_ids):
    """Pages ``page_ids`` of layer ``layer`` of the latent pool ``(L, P,
    page_size // 2, 2W)`` as position rows ``(len(page_ids), page_size,
    W)``: a gather of those pages alone."""
    pages = pool[layer, page_ids]
    return pages.reshape(pages.shape[:-2] + (-1, pages.shape[-1] // 2))


def _latent_rows(p, cfg: ModelConfig, x, positions):
    """x's pool rows (B, T, r + qk_rope): ``[c | k_pe]``."""
    c, krope = _mla_latent(p, cfg, x, positions)
    return jnp.concatenate([c, krope.astype(c.dtype)], -1)


def latent_store_rows(pool, layer, page_idx, offset, rows):
    """Write one row (W,) per batch entry into layer ``layer`` of the whole
    latent ``pool`` at position ``offset[b]`` of page ``page_idx[b]``: the
    window ``[layer, page, offset // 2, (offset % 2) W : + W]`` alone."""
    n, W = rows.shape
    idx = jnp.stack([jnp.broadcast_to(layer, (n,)), page_idx, offset // 2,
                     (offset % 2) * W], axis=-1).astype(jnp.int32)
    return _window_scatter(pool, idx, rows)


def latent_store_chunk(pool, layer, page_table, positions, rows):
    """Write a contiguous chunk of rows (T, W) for ONE slot into layer
    ``layer`` of the whole latent ``pool``: the chunk's pages
    (:func:`_chunk_window`) are gathered, given their rows and written
    back whole, as :func:`paged_store_chunk` does."""
    pos = positions.astype(jnp.int32)
    page_size = 2 * pool.shape[2]
    pids, local, offs = _chunk_window(page_table, pos, page_size)
    pages = latent_pool_rows(pool, layer, pids).at[local, offs].set(
        rows.astype(pool.dtype))
    return _pool_write(pool, layer, pids, 0,
                       pages.reshape((pids.shape[0],) + pool.shape[2:]))


def mla_decode_paged(p, cfg: ModelConfig, x, pools, layer, page_table,
                     positions, use_pallas: bool):
    """One-token MLA decode for a batch of slots against the latent pool.

    Same contract as :func:`gqa_decode_paged`, over ``pools["latent"]``
    (:func:`paged_pools_init`): each slot's new row ``[c | k_pe]`` is
    scattered in place, then every head attends over the slot's latent
    pages in the absorbed form (``kernels.paged_attention.
    latent_attention_pallas``, or its oracle ``kernels.ref.
    latent_attention_ref``), and the latent output is expanded through
    ``w_uv`` here.  Returns ``(out (B,1,D), pools)``."""
    from repro.kernels.paged_attention import latent_attention_pallas
    from repro.kernels.ref import latent_attention_ref

    B = x.shape[0]
    pos = positions.astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, pos[:, None])
    pool = pools["latent"]
    page_size = 2 * pool.shape[2]
    page_idx = page_table[jnp.arange(B), pos // page_size]
    with jax.named_scope("kv_write"):
        pool = latent_store_rows(pool, layer, page_idx, pos % page_size,
                                 _latent_rows(p, cfg, x, pos[:, None])[:, 0])
    q = _mla_absorb(p, cfg, q_nope[:, 0], q_rope[:, 0])
    attend = latent_attention_pallas if use_pallas else latent_attention_ref
    with jax.named_scope("paged_attention"):
        # every layer's pages at once: a free view (L * P, page_size // 2,
        # 2W), the page table offset to this layer's
        n_layers, P = pool.shape[:2]
        lat = attend(q, pool.reshape((n_layers * P,) + pool.shape[2:]),
                     page_table + layer * P, pos + 1, scale=mla_scale(cfg))
    out = _mla_out(p, cfg, lat[..., :cfg.kv_lora_rank], x.dtype)
    return out[:, None], {"latent": pool}


#: context positions one step of the prompt chunk's latent attend reads
LATENT_BLOCK = 1024


def mla_prefill_paged(p, cfg: ModelConfig, x, pools, layer, page_table,
                      positions):
    """Chunk/suffix prefill for ONE slot against the latent pool: the
    chunk's rows are written, then its queries attend, in the absorbed
    form, over the slot's context so far, :data:`LATENT_BLOCK` positions
    at a time with a streaming softmax, reading only the blocks that
    hold positions up to the chunk's last (a loop of dynamic length).
    Absorbed rather than expanded through ``w_uk``/``w_uv``: decode
    reads the pool the same way, and no per-head key or value is
    materialised for the context.  Returns ``(out (1,T,D), pools)``."""
    T = x.shape[1]
    pos = positions.astype(jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, pos)
    pool = pools["latent"]
    with jax.named_scope("kv_write"):
        pool = latent_store_chunk(pool, layer, page_table, pos,
                                  _latent_rows(p, cfg, x, pos)[0])
    q = _mla_absorb(p, cfg, q_nope[0], q_rope[0])       # (T, H, W)
    H, W = q.shape[1], q.shape[2]
    page_size = 2 * pool.shape[2]
    n = max(LATENT_BLOCK // page_size, 1)             # pages a block
    table = jnp.pad(page_table, (0, -page_table.shape[0] % n))
    scale = mla_scale(cfg)

    def block(i, carry):
        acc, m, l = carry
        pids = jax.lax.dynamic_slice_in_dim(table, i * n, n)
        keys = latent_pool_rows(pool, layer, pids).reshape(
            n * page_size, W).astype(jnp.float32)
        s = jnp.einsum("thd,sd->ths", q, keys) * scale
        kpos = i * n * page_size + jnp.arange(n * page_size)
        s = jnp.where(kpos[None, None, :] <= pos[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        e = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (acc * alpha + jnp.einsum("ths,sd->thd", e, keys),
                m_new, l * alpha + jnp.sum(e, -1, keepdims=True))

    n_blocks = pos[T - 1] // (n * page_size) + 1
    acc, _, l = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.zeros((T, H, W), jnp.float32),
         jnp.full((T, H, 1), NEG_INF, jnp.float32),
         jnp.zeros((T, H, 1), jnp.float32)))
    lat = (acc / l)[..., :cfg.kv_lora_rank]
    return _mla_out(p, cfg, lat, x.dtype)[None], {"latent": pool}
