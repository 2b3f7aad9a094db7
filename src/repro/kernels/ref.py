"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def wash_shuffle_ref(x: jax.Array, perm: jax.Array, mask: jax.Array) -> jax.Array:
    """x: (N, D); perm: (N, D); mask: (D,)."""
    shuffled = jnp.take_along_axis(x, perm, axis=0)
    return jnp.where(mask[None, :], shuffled, x)


def flash_attention_ref(q, k, v, *, causal=True, window=None) -> jax.Array:
    """q: (B,S,H,hd); k/v: (B,S,KV,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qf = q.reshape(B, S, KV, g, hd).astype(jnp.float32)
    scores = jnp.einsum("btkgh,bskh->bkgts", qf, k.astype(jnp.float32)) / (hd ** 0.5)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (j <= i)
    if window is not None:
        mask = mask & (j > i - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, page_table, lengths,
                        k_scale=None, v_scale=None) -> jax.Array:
    """Gather-then-attend oracle for the paged decode kernel.

    q: (B,H,hd); k_pool/v_pool: (P,page_size,KV,hd);
    page_table: (B,max_pages) int32; lengths: (B,) int32 -> (B,H,hd).

    Materializes each slot's context contiguously (the two-pass form the
    kernel fuses away) and applies a plain masked softmax — same grouping
    and float32 reductions as ``models.layers.sdpa``.

    ``k_scale``/``v_scale`` (``(P,)`` float32, optional) dequantize int8
    pools: page ``p``'s rows are read as ``pool[p] * scale[p]`` — the
    per-page symmetric scheme of ``models.layers.paged_pools_init``.
    """
    B, H, hd = q.shape
    _, page_size, KV, _ = k_pool.shape
    g = H // KV
    k = k_pool[page_table].reshape(B, -1, KV, hd)  # (B, max_pages*ps, KV, hd)
    v = v_pool[page_table].reshape(B, -1, KV, hd)
    if k_scale is not None:
        ps = jnp.repeat(k_scale[page_table], page_size, axis=1)  # (B, ctx)
        k = k.astype(jnp.float32) * ps[:, :, None, None]
    if v_scale is not None:
        ps = jnp.repeat(v_scale[page_table], page_size, axis=1)
        v = v.astype(jnp.float32) * ps[:, :, None, None]
    qf = q.reshape(B, KV, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bskh->bkgs", qf, k.astype(jnp.float32)) / (hd ** 0.5)
    valid = jnp.arange(k.shape[1]) < lengths[:, None]  # (B, ctx)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def latent_attention_ref(q, pool, page_table, lengths, *, scale: float
                         ) -> jax.Array:
    """Gather-then-attend oracle for the latent (MLA) decode kernel.

    q: (B, H, W) absorbed queries (``[q_nope w_uk^T | q_rope]``); pool:
    (P, page_size // 2, 2W) latent pages, two ``[c | k_pe]`` rows a row
    (``models.layers.paged_pools_init``); page_table: (B, max_pages)
    int32; lengths: (B,) int32 -> (B, H, W) float32, the softmax over
    each slot's first ``lengths`` rows of ``scale * q . row`` applied to
    the rows (the caller keeps the latent part)."""
    B, _, W = q.shape
    rows = pool[page_table].reshape(B, -1, W).astype(jnp.float32)
    scores = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32), rows) * scale
    valid = jnp.arange(rows.shape[1]) < lengths[:, None]
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    return jnp.einsum("bhs,bsd->bhd", jax.nn.softmax(scores, axis=-1), rows)


def rwkv6_scan_ref(r, k, v, w, u) -> jax.Array:
    """r/k/v/w: (B,T,H,hd); u: (H,hd) -> y (B,T,H,hd)."""
    B, T, H, hd = r.shape

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp  # (B,H,hd) each
        kv = jnp.einsum("bhk,bhv->bhkv", k_t.astype(jnp.float32), v_t.astype(jnp.float32))
        y = jnp.einsum(
            "bhk,bhkv->bhv", r_t.astype(jnp.float32), S + u[None, :, :, None] * kv
        )
        S = w_t.astype(jnp.float32)[..., None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    S0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    _, ys = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype)
