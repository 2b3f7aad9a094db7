"""The latent (MLA) decode kernel's share of its roofline, in %: the
larger of its useful FLOPs (each head's scores over the latent and rope
key, and its weighted sum of latents, over each slot's context) over peak
FLOP/s and the bytes of the latent pages it copies, plus queries and
outputs, over peak bytes/s (the architecture's ``decode_kernel_flops``
and ``decode_kernel_bytes``), against its device time in the trace.

The kernel is told by its operands: a Pallas call whose first operand is
an ``s32`` page table (scalar prefetch) and whose pool and queries are
two latent rows wide (``2 (kv_lora_rank + qk_rope_dim)``: the pool holds
two positions a row).  The expert layers' grouped matmuls are Pallas
calls that lead with ``s32`` operands too, so the page-table test alone
(``bench.kernels.paged_attention``) would count them as well."""

from bench import flops
from bench.kernels import paged_attention


def read(ctx):
    t, a = ctx.trace, ctx.arch
    width = (getattr(a, "kv_lora_rank", 0) or 0) + (
        getattr(a, "qk_rope_dim", 0) or 0)
    if t is None or not width:
        return None

    def kernel(op):
        return paged_attention(op) and f",{2 * width}]" in op.text

    if t.op_count(kernel) == 0:
        return None
    share, _ = flops.roofline_share(
        t.op_seconds(kernel), ctx.counters["kernel_flops"],
        ctx.counters["kernel_bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return share
