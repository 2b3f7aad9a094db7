"""Own device time of the mixing layer over device busy time, in %: the
ops whose scope path holds the program's ``wash`` scope (the plan's RNG,
sorts and scatters under ``wash/plan``, the shuffle kernel and its
gathers under ``wash/shuffle``), each counted for its own time as
``Trace.top_ops`` counts it.  Scopes are read from the trace file
(``bench.program_trace``); nothing is read where no op carries the
scope."""

from bench import program_trace as PT

ROOT = PT.checkout_root(__file__)


def read(ctx):
    t = ctx.trace
    if t is None or not t.ops:
        return None
    pt = PT.for_trace(t, ROOT)
    if pt is None or not pt.has_scope("wash"):
        return None
    own = pt.own_seconds(t, lambda scope: "wash" in PT.segments(scope))
    return 100.0 * own / t.busy_s()
