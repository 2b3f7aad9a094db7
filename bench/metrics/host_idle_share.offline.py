"""Share of the window in which the chip is idle while the program does
host work, in %: idle time (no device op running) under an open program
span whose innermost open span is not a wait (``serve.decode_wait``,
``serve.prefill_wait``: a readback, where the host waits on the chip).
Idle time under no program span (the benchmark's own loop) is left out.
Averaged over the chips.  Read from the trace's device ops and the
program's host spans (``bench.program_trace``); nothing is read where the
trace has no device plane or the program wrote no span."""

from bench import program_trace as PT

ROOT = PT.checkout_root(__file__)


def read(ctx):
    t = ctx.trace
    if t is None or not t.ops:
        return None
    pt = PT.for_trace(t, ROOT)
    if pt is None or not pt.spans:
        return None
    host = [(s, e) for s, e, name in PT.innermost(pt.spans)
            if not name.endswith("_wait")]
    idle = sum(PT.overlap(host, s, e)
               for c in t.chips for s, e in PT.idle_intervals(t, c))
    return 100.0 * idle * 1e-9 / len(t.chips) / t.window_s
