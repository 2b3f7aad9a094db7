"""Pallas TPU kernel: paged-attention decode over a block-pool KV cache.

The continuous-batching runtime (``repro.serving.batching``) stores KV in a
shared pool of fixed-size pages, with a per-slot **page table** mapping
logical context positions to pool pages.  The kernel reads pages as
``(num_pages, page_size, KV, hd)``; the runtime hands it the free view
``(L * P, page_size, KV, hd)`` of its ``(L, P, page_size * KV, hd)``
pools with the page table offset to one layer's pages
(``models.layers.paged_kernel_view``).
Decode-time attention then needs a gather of each slot's pages followed by
a masked attend — two HBM passes when written naively in jnp (materialize
``(B, max_pages·page_size, KV, hd)``, then attend).

This kernel fuses the gather INTO the attend: the page table rides in as a
**scalar-prefetch** operand (``pltpu.PrefetchScalarGridSpec``), so the
BlockSpec index map dereferences ``page_table[slot, j]`` and the DMA engine
streams exactly the pages each slot owns from HBM into VMEM — no
contiguous copy of the context ever exists.  Accumulation across a slot's
pages is the standard streaming softmax (running max / sum / accumulator
in VMEM scratch, carried across the sequential page axis of the grid).

Layout per grid step ``(b, j)``: slot ``b`` holds all ``H`` query rows
in VMEM and visits page ``page_table[b, j]`` with every kv head at once.
The pool is viewed as ``(P, page_size·KV, hd)`` (a free reshape of the
contiguous ``(P, page_size, KV, hd)`` layout), so each page is one
``(page_size·KV, hd)`` tile: its two minor dims are whole arrays or
multiples of every dtype's (sublane, lane) tiling, which a block that
squeezes the KV axis out of the pool's second-minor dim is not.  One
``(H, page_size·KV)`` score matmul covers the page; scores that pair a
query with another group's kv head are masked like positions past the
slot's length (GQA groups are ``g = H // KV`` consecutive query rows).
Pages past a slot's length are skipped with ``pl.when`` (no VPU/MXU work
is spent on fully-masked pages).

:func:`latent_attention_pallas` is the same schedule over a latent (MLA)
pool: one ``(page_size, kv_lora_rank + rope)`` page a grid step, read by
every head of the slot.

``interpret=None`` auto-detects like ``wash_shuffle``: compiled on TPU,
interpret mode elsewhere (CPU timings are correctness-only).  The pure-jnp
oracle is :func:`repro.kernels.ref.paged_attention_ref`, parity-asserted
in ``tests/test_kernels.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import resolve_interpret

NEG_INF = -1e30


def _paged_kernel(
    *refs,
    page_size: int, scale: float, quantized: bool,
):
    # scalar-prefetch refs lead: pt (B, max_pages) i32, lengths (B,) i32,
    # then — quantized pools only — per-slot-per-page dequant scales
    # ks/vs (B, max_pages) f32 (pre-gathered through the page table, so
    # the kernel never indexes the (P,) scale vectors itself)
    if quantized:
        pt_ref, len_ref, ks_ref, vs_ref = refs[:4]
        refs = refs[4:]
    else:
        pt_ref, len_ref = refs[:2]
        ks_ref = vs_ref = None
        refs = refs[2:]
    # (1, R) kv head / in-page position of each page row, (H, 1) kv head
    # of each query row; R = page_size * KV
    col_head_ref, col_pos_ref, row_head_ref = refs[:3]
    q_ref, k_ref, v_ref = refs[3:6]  # (H, hd), (R, hd), (R, hd)
    o_ref = refs[6]                  # (H, hd)
    acc_ref, m_ref, l_ref = refs[7:]  # VMEM scratch: (H, hd), (H, 1), (H, 1)

    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip pages wholly past this slot's context (their DMA already
    # happened, but no VPU/MXU work is spent on fully-masked scores)
    @pl.when(j * page_size < length)
    def _page():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[b, j]
        scores = jax.lax.dot_general(           # q @ k.T: (H, R)
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        valid = ((col_head_ref[...] == row_head_ref[...])
                 & (j * page_size + col_pos_ref[...] < length))
        scores = jnp.where(valid, scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        vpage = v_ref[...].astype(jnp.float32)
        if quantized:
            vpage = vpage * vs_ref[b, j]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, vpage, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One-token paged attention for a batch of serving slots.

      q          : (B, H, hd)   — the current token's query per slot
      k_pool     : (P, page_size, KV, hd) — shared K page pool
      v_pool     : (P, page_size, KV, hd) — shared V page pool
      page_table : (B, max_pages) int32 — pool page id per logical page
                   (unused tail entries may point anywhere; they are masked)
      lengths    : (B,) int32 — valid context tokens per slot (>= 1)
      k_scale /
      v_scale    : (P,) float32, optional — per-page dequant scales for
                   int8 pools (``models.layers.paged_pools_init`` with
                   ``kv_dtype="int8"``); pages are read as
                   ``pool[p] * scale[p]``.  Both or neither.

    Returns (B, H, hd).  GQA: ``H % KV == 0``; queries are grouped by kv
    head exactly as :func:`repro.models.layers.sdpa` groups them.
    """
    B, H, hd = q.shape
    P, page_size, KV, _ = k_pool.shape
    max_pages = page_table.shape[1]
    g = H // KV
    scale = hd ** -0.5
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale, or neither")

    R = page_size * KV
    pt = page_table.astype(jnp.int32)
    kernel = functools.partial(
        _paged_kernel, page_size=page_size, scale=scale, quantized=quantized,
    )
    rows = jnp.arange(R, dtype=jnp.int32)[None, :]
    masks = (rows % KV, rows // KV,
             jnp.arange(H, dtype=jnp.int32)[:, None] // g)
    # quantized pools prepend two more scalar-prefetch operands (dequant
    # scales pre-gathered to (B, max_pages)); index-map lambdas take one
    # trailing arg per prefetch operand
    n_pref = 4 if quantized else 2

    def _whole(a):
        return pl.BlockSpec(a.shape, lambda b, j, *pref: (0, 0))

    def _q_map(b, j, *pref):
        return (b, 0, 0)

    def _page_map(b, j, *pref):
        return (pref[0][b, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(B, max_pages),
        in_specs=[_whole(a) for a in masks] + [
            pl.BlockSpec((None, H, hd), _q_map),
            pl.BlockSpec((None, R, hd), _page_map),
            pl.BlockSpec((None, R, hd), _page_map),
        ],
        out_specs=pl.BlockSpec((None, H, hd), _q_map),
        scratch_shapes=[
            pltpu.VMEM((H, hd), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    prefetch = (pt, lengths.astype(jnp.int32))
    if quantized:
        prefetch += (k_scale[pt].astype(jnp.float32),
                     v_scale[pt].astype(jnp.float32))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_attention",
    )(*prefetch, *masks, q, k_pool.reshape(P, R, hd),
      v_pool.reshape(P, R, hd))


# ---------------------------------------------------------------------------
# latent (MLA) decode
# ---------------------------------------------------------------------------


def _latent_kernel(pt_ref, len_ref, q_ref, page_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, page_size: int, scale: float):
    # q (2H, 2W): each head's query in the first and, below, the second
    # half of a row; page (page_size // 2, 2W): positions 2r and 2r + 1 of
    # the page in row r.  So one matmul scores the even positions (rows
    # :H) and the odd ones (rows H:), and p @ page sums their rows
    # (the half of each row the caller keeps).
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]
    H = q_ref.shape[0] // 2

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * page_size < length)
    def _page():
        q = q_ref[...] * scale
        page = page_ref[...].astype(jnp.float32)
        scores = jax.lax.dot_general(           # q @ page.T: (2H, ps // 2)
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        pair = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        pos = j * page_size + 2 * pair + (row >= H).astype(jnp.int32)
        scores = jnp.where(pos < length, scores, NEG_INF)
        s_even, s_odd = scores[:H], scores[H:]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.maximum(
            jnp.max(s_even, axis=-1, keepdims=True),
            jnp.max(s_odd, axis=-1, keepdims=True)))
        alpha = jnp.exp(m_prev - m_new)
        p_even = jnp.exp(s_even - m_new)
        p_odd = jnp.exp(s_odd - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            p_even, axis=-1, keepdims=True) + jnp.sum(
            p_odd, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[:H] = acc_ref[:H] * alpha + jnp.dot(
            p_even, page, preferred_element_type=jnp.float32)
        acc_ref[H:] = acc_ref[H:] * alpha + jnp.dot(
            p_odd, page, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[:H] = acc_ref[:H] / l
        o_ref[H:] = acc_ref[H:] / l


def latent_attention_pallas(
    q: jax.Array,
    pool: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    *,
    scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One-token latent attention (MLA, absorbed form) for a batch of
    serving slots.

      q          : (B, H, W) — each head's absorbed query
                   ``[q_nope w_uk^T | q_rope]``, W = kv_lora_rank + rope
      pool       : (P, page_size // 2, 2W) — latent pages, one ``[c |
                   k_pe]`` row a position and two positions a row
                   (``models.layers.paged_pools_init``), shared by every
                   head
      page_table : (B, max_pages) int32 — pool page id per logical page
      lengths    : (B,) int32 — valid context tokens per slot (>= 1)

    Returns (B, H, W) float32: the softmax over the slot's rows of
    ``scale * q . row``, applied to the rows; the caller keeps the latent
    part and expands it through ``w_uv``.  Grid ``(B, max_pages)`` as in
    :func:`paged_attention_pallas`: the page table is scalar prefetch,
    step ``(b, j)`` copies page ``page_table[b, j]`` (all H heads read
    it), consecutive visits of one page (the scratch page past a slot's
    length) copy it once, and pages past the length do no work.  The
    oracle is :func:`repro.kernels.ref.latent_attention_ref`."""
    B, H, W = q.shape
    _, half, W2 = pool.shape
    max_pages = page_table.shape[1]
    kernel = functools.partial(_latent_kernel, page_size=2 * half,
                               scale=scale)
    q = q.astype(jnp.float32)
    zeros = jnp.zeros_like(q)
    # (B, 2H, 2W): [q | 0] over [0 | q]
    q2 = jnp.concatenate([jnp.concatenate([q, zeros], -1),
                          jnp.concatenate([zeros, q], -1)], 1)

    def _q_map(b, j, pt, lens):
        return (b, 0, 0)

    def _page_map(b, j, pt, lens):
        return (pt[b, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[pl.BlockSpec((None, 2 * H, W2), _q_map),
                  pl.BlockSpec((None, half, W2), _page_map)],
        out_specs=pl.BlockSpec((None, 2 * H, W2), _q_map),
        scratch_shapes=[
            pltpu.VMEM((2 * H, W2), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 2 * H, W2), jnp.float32),
        interpret=resolve_interpret(interpret),
        name="latent_attention",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q2, pool)
    # even positions' sums in the first half of rows :H, odd in the
    # second half of rows H:
    return out[:, :H, :W] + out[:, H:, W:]
