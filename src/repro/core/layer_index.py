"""Per-leaf layer indices for the layer-wise probability schedule (Eq. 6).

The paper assigns each parameter a depth l in [0, L-1]; the shuffle
probability is p_l = p * (1 - l/(L-1)): the first layer shuffles with the
base probability, the last layer never shuffles.

Convention used by every model in ``repro.models``:

  * token/patch/frame embeddings            -> depth 0
  * transformer block i (or conv stage i)   -> depth i + 1
  * final norm / lm head / classifier head  -> depth L_total - 1

We infer depths from pytree paths: a leaf whose path contains the dict key
``blocks`` (or ``enc_blocks``/``dec_blocks``) followed by a sequence index i
gets depth i+1; paths containing ``embed`` get 0; everything else gets the
maximum depth.  Models with unusual structure can provide explicit overrides.

A model with leading dense blocks stacks them apart, under
``dense_blocks`` (``repro.models.transformer.segments``): its ``k`` blocks
take depths 1..k, and the ``blocks`` stack after them k+1..num_blocks.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

_BLOCK_KEYS = ("blocks", "enc_blocks", "dec_blocks", "stages")
_EMBED_RE = re.compile(r"(embed|patch_proj|frame_proj|conv_in|tok_)")


def _path_entries(path) -> list:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(int(p.idx))
        else:  # pragma: no cover - defensive
            out.append(str(p))
    return out


def leaf_depth(path, num_blocks: int) -> int:
    """Depth in [0, L-1] with L = num_blocks + 2 (embed + blocks + head)."""
    entries = _path_entries(path)
    l_total = num_blocks + 2
    for i, e in enumerate(entries):
        if isinstance(e, str) and e in _BLOCK_KEYS:
            nxt = entries[i + 1] if i + 1 < len(entries) else None
            if isinstance(nxt, int):
                return min(nxt + 1, l_total - 1)
            m = re.search(r"(\d+)$", str(nxt)) if nxt is not None else None
            if m:
                return min(int(m.group(1)) + 1, l_total - 1)
    joined = "/".join(str(e) for e in entries).lower()
    if _EMBED_RE.search(joined):
        return 0
    return l_total - 1


def _is_scanned_blocks(path, leaf, num_blocks: int) -> bool:
    """True for stacked-block leaves: path hits a block key with no
    per-layer sequence index, and the leading dim equals num_blocks."""
    entries = _path_entries(path)
    for i, e in enumerate(entries):
        if isinstance(e, str) and e in _BLOCK_KEYS:
            nxt = entries[i + 1] if i + 1 < len(entries) else None
            if not isinstance(nxt, int):
                return hasattr(leaf, "shape") and leaf.shape and leaf.shape[0] == num_blocks
    return False


def _dense_count(flat) -> int:
    """Leading dense blocks of a params tree: the stack ``dense_blocks``."""
    for path, leaf in flat:
        if _path_entries(path)[:1] == ["dense_blocks"]:
            return int(leaf.shape[0])
    return 0


def infer_layer_ids(params: PyTree, num_blocks: int) -> PyTree:
    """Pytree (same structure as params) of depths.

    Leaves are ints, except stacked-block leaves (scanned models: one leaf
    spans all blocks along axis 0) which get an np.arange depth vector so
    the Eq. 6 schedule stays per-layer exact.
    """
    import numpy as np

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    k = _dense_count(flat)
    depths = []
    for path, leaf in flat:
        top = _path_entries(path)[:1]
        if k and top == ["dense_blocks"]:
            depths.append(np.arange(1, k + 1))
        elif k and top == ["blocks"]:
            depths.append(np.arange(k + 1, num_blocks + 1))
        elif _is_scanned_blocks(path, leaf, num_blocks):
            depths.append(np.arange(1, num_blocks + 1))
        else:
            depths.append(leaf_depth(path, num_blocks))
    return jax.tree_util.tree_unflatten(treedef, depths)


def total_layers(num_blocks: int) -> int:
    return num_blocks + 2


def stage_layer_bounds(num_blocks: int, num_stages: int):
    """Contiguous ``[lo, hi)`` block ranges per pipeline stage.

    Near-even split: stage ``s`` owns blocks ``[s*L//S, (s+1)*L//S)``, so
    uneven layer counts (kimi's 61 blocks over 8 stages) stay legal for the
    planner's per-stage accounting; the training/serving engines additionally
    require ``L % S == 0`` so stage shards share one shape.
    """
    if num_stages < 1:
        raise ValueError(f"num_stages must be >= 1, got {num_stages}")
    return tuple(
        (s * num_blocks // num_stages, (s + 1) * num_blocks // num_stages)
        for s in range(num_stages)
    )


def stage_of_depth(depth: int, num_blocks: int, num_stages: int) -> int:
    """Owner stage of a leaf by its depth index (see :func:`leaf_depth`).

    Depth 0 (embeddings) lives on stage 0; depth ``num_blocks + 1`` (final
    norm / head) on the last stage; block ``b`` (depth ``b + 1``) on the
    stage whose :func:`stage_layer_bounds` range contains it.
    """
    if depth <= 0:
        return 0
    if depth >= num_blocks + 1:
        return num_stages - 1
    b = depth - 1
    for s, (lo, hi) in enumerate(stage_layer_bounds(num_blocks, num_stages)):
        if lo <= b < hi:
            return s
    return num_stages - 1  # pragma: no cover - bounds always tile [0, L)


def depth_histogram(params: PyTree, num_blocks: int) -> dict:
    """Diagnostic: scalar count per depth (used by comm-volume accounting)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    hist: dict[int, int] = {}
    for path, leaf in flat:
        d = leaf_depth(path, num_blocks)
        size = int(jnp.size(leaf))
        hist[d] = hist.get(d, 0) + size
    return hist
