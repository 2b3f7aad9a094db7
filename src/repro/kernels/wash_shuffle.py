"""Pallas TPU kernels for the WASH shuffle apply (paper Eq. 3).

The dense shuffle is three HBM passes when written naively in jnp
(uniforms → argsort-take → where).  These kernels fuse the *apply* phase —
masked cross-member permute-gather — into a single pass over VMEM tiles of
the stacked (N, D) leaf:

    out[n, i] = x[perm[n, i], i]   if mask[i]
              = x[n, i]            otherwise

TPU adaptation: the ensemble axis N is tiny (2–16), so the per-coordinate
gather along it is an N-way select (VPU compare+select, no hardware
gather).  The coordinate axis is folded into ``(rows, 128)`` lane tiles
and the members ride a leading, untiled block axis, so each member's tile
is a whole ``(block_rows, 128)`` vreg stack: no sublane shuffles, and the
same layout is legal for every dtype (row counts are multiples of 32, the
int8 tile height, or the whole array).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compat import resolve_interpret

LANES = 128
# a multiple of every dtype's row tiling (f32 8, bf16 16, int8 32)
ROW_TILE = 32
#: default coordinates per grid step: 512 rows of 128 lanes per member
BLOCK_D = 65536


def _tiling(d: int, block_d: int):
    """(block_rows, padded_rows) for a D-coordinate leaf: ``block_d`` is
    rounded down to whole ``ROW_TILE`` row groups; a leaf smaller than one
    block is a single block spanning the whole (always legal) array."""
    rows = -(-d // LANES)
    block_rows = max(ROW_TILE, (block_d // LANES) // ROW_TILE * ROW_TILE)
    if rows <= block_rows:
        return rows, rows
    return block_rows, -(-rows // block_rows) * block_rows


def _to_tiles(a: jax.Array, padded_rows: int) -> jax.Array:
    """(..., D) → (..., padded_rows, 128), zero-padded along D."""
    pad = padded_rows * LANES - a.shape[-1]
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(a.shape[:-1] + (padded_rows, LANES))


def _dense_kernel(x_ref, perm_ref, mask_ref, out_ref, *, n: int):
    keep = mask_ref[...] == 0                    # (rows, 128)
    for m in range(n):
        perm = perm_ref[m]                       # (rows, 128) int32
        gathered = x_ref[0]
        for src in range(1, n):
            gathered = jnp.where(perm == src, x_ref[src], gathered)
        out_ref[m] = jnp.where(keep, x_ref[m], gathered)


def _bucketed_kernel(x_ref, shift_ref, out_ref, *, n: int):
    shift = shift_ref[...].astype(jnp.int32)     # (rows, 128)
    for m in range(n):
        out = x_ref[m]
        for s in range(1, n):
            # bucket s: member m takes member (m + s) mod n's value
            out = jnp.where(shift == s, x_ref[(m + s) % n], out)
        out_ref[m] = out


def _call(kernel, x, extras, block_rows, interpret, name):
    """Run ``kernel`` over (n, rows, 128) ``x`` plus per-coordinate
    operands ``extras`` (leading member axis or not), tiled by rows, as
    the kernel ``name``."""
    n, rows, _ = x.shape

    def spec(a):
        if a.ndim == 3:
            return pl.BlockSpec((a.shape[0], block_rows, LANES),
                                lambda i: (0, i, 0))
        return pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(kernel, n=n),
        grid=(rows // block_rows,),
        in_specs=[spec(x)] + [spec(a) for a in extras],
        out_specs=spec(x),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),
        name=name,
    )(x, *extras)


def wash_shuffle_pallas(
    x: jax.Array,
    perm: jax.Array,
    mask: jax.Array,
    *,
    block_d: int = BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """x: (N, D); perm: (N, D) int32; mask: (D,) bool -> shuffled (N, D)."""
    n, d = x.shape
    block_rows, rows = _tiling(d, block_d)
    out = _call(
        _dense_kernel, _to_tiles(x, rows),
        [_to_tiles(perm.astype(jnp.int32), rows),
         _to_tiles(mask.astype(jnp.int32), rows)],
        block_rows, interpret, "wash_shuffle_dense",
    )
    return out.reshape(n, rows * LANES)[:, :d]


def bucketed_shuffle_pallas(
    x: jax.Array,
    idx: jax.Array,
    *,
    block_d: int = BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Bucketed WASH apply (core.shuffle's TPU-native mode) as one fused
    VMEM pass over the stacked (N, D) leaf.

    ``idx``: (N, k_per) int32 plan with pairwise-disjoint rows; bucket s
    applies the global cyclic shift θ̂_n = θ_{(n+s) mod N} on its
    coordinates, bucket 0 is the identity.  The bucket structure is first
    scattered into a per-coordinate shift map (one (D,) int8 scatter
    outside the kernel — a byte per coordinate, not an (N, D) int32
    permutation), and the kernel selects, for each member, the row its
    shift points at."""
    n, d = x.shape
    if n > 127:
        raise ValueError(f"population {n} exceeds the int8 shift map")
    shift = jnp.zeros((d,), jnp.int8)
    if n > 1:  # bucket 0 is the identity; rows are disjoint → one scatter
        shift = shift.at[idx[1:]].set(
            jnp.arange(1, n, dtype=jnp.int8)[:, None]
        )
    block_rows, rows = _tiling(d, block_d)
    out = _call(
        _bucketed_kernel, _to_tiles(x, rows), [_to_tiles(shift, rows)],
        block_rows, interpret, "wash_shuffle",
    )
    return out.reshape(n, rows * LANES)[:, :d]
