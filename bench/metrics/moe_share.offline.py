"""Own device time of the expert layers over device busy time, in %: the
ops whose scope path holds the program's ``moe`` scope (the router under
``moe/route``, the held experts' sort and scatter under ``moe/experts``,
the shared experts under ``moe/shared``), and the held experts' grouped
matmuls, each counted for its own time as ``Trace.top_ops`` counts it.
The TPU compiler expands ``jax.lax.ragged_dot``, which the program runs
only in the expert layer, into Pallas calls named ``ragged-dot-*`` whose
metadata holds no scope, so those are matched by name.  Scopes are read
from the trace file (``bench.program_trace``); nothing is read where no
op carries the scope."""

from bench import program_trace as PT

ROOT = PT.checkout_root(__file__)
#: name prefix of the ops the compiler makes of a grouped matmul
GROUPED = "ragged-dot"


def read(ctx):
    t = ctx.trace
    if t is None or not t.ops:
        return None
    pt = PT.for_trace(t, ROOT)
    if pt is None or not pt.has_scope("moe"):
        return None

    def moe(chip, op):
        return (op.name.startswith(GROUPED)
                or "moe" in PT.segments(pt.scope(chip, op)))

    own = sum(s for c in t.chips for op, s in t.self_times(c) if moe(c, op))
    return 100.0 * own * 1e-9 / len(t.chips) / t.busy_s()
