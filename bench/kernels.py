"""How the trace names the program's kernels.

Neither Pallas kernel is given a name by the program.  In the trace each
is a ``custom-call`` op with ``custom_call_target="tpu_custom_call"``,
named after whatever encloses it (``closed_call.<n>`` and ``body.<n>`` in
the cells' programs, ``paged_attention_pallas.<n>`` when called alone).
So each is told by its operands: the paged-attention kernel's first
operand is the ``s32`` page table (scalar prefetch), and the shuffle
kernel reads an ``s8`` shift map.
"""

from __future__ import annotations

PALLAS = 'custom_call_target="tpu_custom_call"'


def paged_attention(op) -> bool:
    return PALLAS in op.text and "custom-call(s32[" in op.text


def wash_shuffle(op) -> bool:
    return PALLAS in op.text and " s8[" in op.text
