"""The bucketed WASH shuffle kernel's share of its roofline, in %: the
bytes it must move (every member's tiles read and written, and the int8
shift map, ``flops.shuffle_step_bytes`` per mixing step) over peak HBM
bytes/s, against its device time in the trace.  The kernel has no name
of its own: it is matched as the Pallas custom call of the training
program, the only one there."""

from bench import flops
from bench.kernels import wash_shuffle as kernel


def read(ctx):
    t = ctx.trace
    if t is None or t.op_count(kernel) == 0:
        return None
    share, _ = flops.roofline_share(
        t.op_seconds(kernel), 0.0, ctx.counters["shuffle_bytes"],
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return share
