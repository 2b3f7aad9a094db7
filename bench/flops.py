"""Operations and bytes of the work a cell does, from its shapes alone.

Model FLOPs count each multiply-add as 2 and only the work the algorithm
needs: causal attention over the positions a query may see, nothing that
is recomputed, masked or padded.  Bytes count what a kernel must move
between HBM and the chip at least once.  What depends on an
architecture's sizes is counted by its module,
``bench/architectures/<name>.py``; what is here reads only shapes.
"""

from __future__ import annotations

from typing import Iterable

import jax
import numpy as np

from bench.reference import leaf_depths


def causal_pairs(start: int, length: int) -> int:
    """(query, key) pairs of ``length`` queries at positions start.. that
    each attend to every position up to their own."""
    return length * start + length * (length + 1) // 2


def paged_attention_dmas(pages_per_slot: Iterable[int], max_pages: int
                         ) -> int:
    """Pages one paged-attention call copies in: its grid visits each
    slot's page-table row in order and skips the copy where a block's page
    is the one just visited.  A slot's row is its pages (0 for an idle
    slot), then the scratch page up to ``max_pages``."""
    dmas, prev_scratch = 0, False
    for n in pages_per_slot:
        dmas += n
        if n:
            prev_scratch = False
        if n < max_pages:
            if not prev_scratch:
                dmas += 1
            prev_scratch = True
    return dmas


LANES, ROW_TILE, BLOCK_D = 128, 32, 65536


def _padded_coords(d: int) -> int:
    """Coordinates the shuffle kernel tiles a (N, d) leaf into."""
    rows = -(-d // LANES)
    block_rows = max(ROW_TILE, (BLOCK_D // LANES) // ROW_TILE * ROW_TILE)
    if rows > block_rows:
        rows = -(-rows // block_rows) * block_rows
    return rows * LANES


def shuffled_leaves(member, num_layers: int, n: int, base_p: float):
    """Coordinates of each leaf of ``member`` (a tree of arrays or shapes)
    that a WASH step shuffles: the leaves at a depth whose probability
    keeps at least one coordinate per member (``reference.wash_plan``'s
    rule; the head's depth has probability 0)."""
    last = num_layers + 1
    out = []
    for leaf, depth in zip(jax.tree_util.tree_leaves(member),
                           leaf_depths(member, num_layers)):
        if isinstance(depth, np.ndarray):     # stacked: one depth a layer
            d, depths = int(np.prod(leaf.shape[1:])), list(depth)
        else:
            d, depths = int(np.prod(leaf.shape)), [depth]
        count = sum(int(round(base_p * (1 - dep / last) * d)) for dep in depths)
        if count // n > 0:
            out.append(d * len(depths))
    return out


def shuffle_step_bytes(member, num_layers: int, n: int, base_p: float,
                       itemsize: int = 2) -> int:
    """Bytes the bucketed shuffle kernel moves in one WASH step: it reads
    every member's tiles and the int8 shift map and writes every member's
    tiles, for each shuffled leaf."""
    total = 0
    for d in shuffled_leaves(member, num_layers, n, base_p):
        p = _padded_coords(d)
        total += 2 * n * p * itemsize + p
    return int(total)


def pages_of(length: int, page_size: int) -> int:
    return max(-(-int(length) // page_size), 1)


def roofline_share(seconds: float, flops_: float, bytes_: float,
                   peak_flops: float, peak_bytes: float):
    """(share in %, bound) of a kernel's time: the least time the chip
    could take, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s, over the time it took."""
    t_flops = (flops_ or 0.0) / peak_flops
    t_bytes = (bytes_ or 0.0) / peak_bytes
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
