"""The paged-attention kernel's share of its roofline, in %: the larger
of its useful FLOPs (causal attention over each slot's context) over peak
FLOP/s and the bytes of the pages it reads, plus queries and outputs,
over peak bytes/s (the architecture's ``decode_kernel_flops`` and
``decode_kernel_bytes``), against its device time in the trace.  At these
sizes bytes bound it.  The kernel is matched by its op's name
(``bench.kernels``)."""

from bench import flops
from bench.kernels import paged_attention as kernel


def read(ctx):
    t = ctx.trace
    if t is None or t.op_count(kernel) == 0:
        return None
    share, _ = flops.roofline_share(
        t.op_seconds(kernel), ctx.counters["kernel_flops"],
        ctx.counters["kernel_bytes"], ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return share
