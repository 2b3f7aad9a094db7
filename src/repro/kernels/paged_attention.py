"""Pallas TPU kernel: paged-attention decode over a block-pool KV cache.

The continuous-batching runtime (``repro.serving.batching``) stores KV in a
shared pool of fixed-size pages, ``(num_pages, page_size, KV, hd)``, with a
per-slot **page table** mapping logical context positions to pool pages.
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
    )(*prefetch, *masks, q, k_pool.reshape(P, R, hd),
      v_pool.reshape(P, R, hd))
