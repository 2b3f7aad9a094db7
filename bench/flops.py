"""Operations and bytes of the work a cell does, from its shapes alone.

Model FLOPs count each multiply-add as 2 and only the work the algorithm
needs: causal attention over the positions a query may see, nothing that
is recomputed, masked or padded.  Bytes count what a kernel must move
between HBM and the chip at least once.
"""

from __future__ import annotations

from typing import Iterable

from bench.reference import Arch


def block_matmul_params(a: Arch) -> int:
    """Matmul weights of one decoder block (q, k, v, o and the SwiGLU)."""
    D, hd = a.d_model, a.head_dim
    attn = D * (a.num_heads + 2 * a.num_kv_heads) * hd + a.num_heads * hd * D
    return attn + 3 * D * a.d_ff


def head_params(a: Arch) -> int:
    return a.d_model * a.vocab_size


def causal_pairs(start: int, length: int) -> int:
    """(query, key) pairs of ``length`` queries at positions start.. that
    each attend to every position up to their own."""
    return length * start + length * (length + 1) // 2


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


def paged_attention_bytes(a: Arch, dmas: int, slots: int, page_size: int,
                          itemsize: int = 2) -> int:
    """Bytes of one paged-attention call (one layer): ``dmas`` K and V
    pages, each slot's query read and its output written once."""
    qo = 2 * slots * a.num_heads * a.head_dim * itemsize
    return 2 * dmas * kv_page_bytes(a, page_size, itemsize) + qo


def paged_attention_flops(a: Arch, lengths: Iterable[int]) -> int:
    return sum(attention_flops(a, n) for n in lengths)


LANES, ROW_TILE, BLOCK_D = 128, 32, 65536


def _padded_coords(d: int) -> int:
    """Coordinates the shuffle kernel tiles a (N, d) leaf into."""
    rows = -(-d // LANES)
    block_rows = max(ROW_TILE, (BLOCK_D // LANES) // ROW_TILE * ROW_TILE)
    if rows > block_rows:
        rows = -(-rows // block_rows) * block_rows
    return rows * LANES


def shuffled_leaves(a: Arch, n: int, base_p: float):
    """Coordinates of each leaf that a WASH step shuffles: the leaves at
    a depth whose probability keeps at least one coordinate per member
    (the embedding and the blocks; the head's depth has probability 0)."""
    D, hd, F = a.d_model, a.head_dim, a.d_ff
    L = a.num_layers
    last = L + 1
    leaves = [(a.vocab_size * D, [0])]
    block = [D * a.num_heads * hd, D * a.num_kv_heads * hd,
             D * a.num_kv_heads * hd, a.num_heads * hd * D,
             D * F, D * F, F * D, D, D]
    if a.qkv_bias:
        block += [a.num_heads * hd, a.num_kv_heads * hd, a.num_kv_heads * hd]
    if a.qk_norm:
        block += [hd, hd]
    leaves += [(d, list(range(1, L + 1))) for d in block]
    out = []
    for d, depths in leaves:
        count = sum(int(round(base_p * (1 - dep / last) * d)) for dep in depths)
        if count // n > 0:
            out.append(d * len(depths))
    return out


def shuffle_step_bytes(a: Arch, n: int, base_p: float, itemsize: int = 2
                       ) -> int:
    """Bytes the bucketed shuffle kernel moves in one WASH step: it reads
    every member's tiles and the int8 shift map and writes every member's
    tiles, for each shuffled leaf."""
    total = 0
    for d in shuffled_leaves(a, n, base_p):
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
