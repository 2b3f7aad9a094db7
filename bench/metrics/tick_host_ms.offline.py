"""Host time of a serving tick, in ms: the median, over the window's
``serve.tick`` spans that did work (the program's request driver, one
tick a round of admission, a prompt chunk and a decode step; a tick that
holds no other program span found nothing to do), of each tick's length
less the parts under ``serve.decode_wait`` and ``serve.prefill_wait``
(the readbacks, where the host waits on the chip).  Read from the
program's host spans in the trace file (``bench.program_trace``); nothing
is read where the program wrote no tick span."""

import bisect
import statistics

from bench import program_trace as PT

ROOT = PT.checkout_root(__file__)
WAITS = ("serve.decode_wait", "serve.prefill_wait")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    pt = PT.for_trace(t, ROOT)
    if pt is None:
        return None
    inner = sorted(s for name, s, _ in pt.spans if name != "serve.tick")
    ticks = [(s, e) for s, e in pt.named("serve.tick")
             if bisect.bisect_left(inner, s) < bisect.bisect_left(inner, e)]
    if not ticks:
        return None
    waits = sorted(w for name in WAITS for w in pt.named(name))
    host = [(e - s) - PT.overlap(waits, s, e) for s, e in ticks]
    return statistics.median(host) * 1e-6
