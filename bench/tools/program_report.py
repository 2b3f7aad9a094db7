"""Summarize a traced run by the program's own names: device time by
``jax.named_scope``, host time by program span, and the longest idle gaps
with the program span each falls in.

    python3 bench/tools/program_report.py bench_out/trace/<cell>.<seed>

Prints:

- device own time (as ``Trace.top_ops`` counts it) by top-level scope
  (the first of the program's scopes on an op's path) and by innermost
  scope, each as a share of busy time; ops with none under ``(none)``,
  with the unscoped ops that take the most time;
- for each program span name, its count, host seconds and median length,
  and whether every ``serve.decode_step`` holds its four phases;
- the ten longest idle gaps, each with the innermost program span that
  covers most of it;
- the idle share of the window, and the part of it under program host
  work (``host_idle_share``).
"""

from __future__ import annotations

import pathlib
import statistics
import sys
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_trace as PT, trace  # noqa: E402

#: the program's scopes (``repro.train.engine``, ``repro.core.mixing``,
#: ``repro.models``, ``repro.serving.batching``)
SCOPES = ("loss", "optimizer", "wash", "plan", "shuffle", "attention",
          "kv_write", "paged_attention", "mlp", "logits", "sample")
PHASES = ("serve.decode_prepare", "serve.decode_dispatch",
          "serve.decode_wait", "serve.decode_commit")


def scoped(path: str, inner: bool) -> str:
    """The first (or, ``inner``, the last run of nested) program scope on
    a scope path: ``wash`` or ``wash/plan``; ``(none)`` where none."""
    names = [s for s in PT.segments(path) if s in SCOPES]
    if not names:
        return "(none)"
    return "/".join(names) if inner else names[0]


def by_scope(tr, pt, inner: bool) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for c in tr.chips:
        for op, t in tr.self_times(c):
            key = scoped(pt.scope(c, op), inner)
            out[key] = out.get(key, 0.0) + t * 1e-9 / len(tr.chips)
    return out


def unscoped_ops(tr, pt, k: int = 8) -> List:
    out: Dict[str, float] = {}
    for c in tr.chips:
        for op, t in tr.self_times(c):
            if scoped(pt.scope(c, op), False) == "(none)":
                key = f"{op.module}/{op.name} [{pt.scope(c, op) or '-'}]"
                out[key] = out.get(key, 0.0) + t * 1e-9 / len(tr.chips)
    return sorted(out.items(), key=lambda kv: -kv[1])[:k]


def gap_span(pieces, s: int, e: int) -> Optional[str]:
    best, most = None, 0
    for a, b, name in pieces:
        ov = min(b, e) - max(a, s)
        if ov > most:
            best, most = name, ov
    return best


def main(path: str) -> None:
    tr = trace.load(path)
    # the checkout the trace was written in
    parts = pathlib.Path(path).resolve().parts
    root = (pathlib.Path(*parts[:parts.index("bench_out")])
            if "bench_out" in parts else ROOT)
    pt = PT.for_trace(tr, root)
    if pt is None:
        sys.exit(f"no trace file under {root / PT.TRACE_DIR} holds the "
                 f"window of {path}")
    busy = tr.busy_s() or float("nan")
    print(f"window {tr.window_s:.6f} s, busy {busy:.6f} s, chips {tr.chips}")
    for inner in (False, True):
        print("device own time by", "innermost" if inner else "top-level",
              "scope:")
        for key, s in sorted(by_scope(tr, pt, inner).items(),
                             key=lambda kv: -kv[1]):
            print(f"  {s:12.6f} s {100 * s / busy:7.2f} %  {key}")
    print("unscoped ops with the most own time:")
    for key, s in unscoped_ops(tr, pt):
        print(f"  {s:12.6f} s {100 * s / busy:7.2f} %  {key[:160]}")

    print("program spans (count, host s, median ms):")
    names = sorted({n for n, _, _ in pt.spans})
    for name in names:
        lens = [e - s for s, e in pt.named(name)]
        print(f"  {name:24s} {len(lens):7d} {sum(lens) * 1e-9:10.4f} "
              f"{statistics.median(lens) * 1e-6:10.4f}")
    steps = pt.named("serve.decode_step")
    if steps:
        whole = sum(
            1 for s, e in steps
            if {n for n, a, b in pt.spans if n in PHASES and s <= a
                and b <= e} == set(PHASES))
        print(f"decode steps holding all four phases: {whole} of "
              f"{len(steps)}")

    pieces = PT.innermost(pt.spans)
    print("longest idle gaps (s, program span, benchmark span):")
    chip = tr.chips[0] if tr.chips else None
    if chip is not None:
        gaps = sorted(PT.idle_intervals(tr, chip), key=lambda g: g[0] - g[1])
        bench_names = tr.idle_gaps(10)
        for (s, e), (bname, _) in zip(gaps[:10], bench_names):
            print(f"  {(e - s) * 1e-9:.6f}  {gap_span(pieces, s, e)}  "
                  f"{bname}")
        host = [(a, b) for a, b, n in pieces if not n.endswith("_wait")]
        idle = sum(e - s for s, e in PT.idle_intervals(tr, chip))
        under = sum(PT.overlap(host, s, e)
                    for s, e in PT.idle_intervals(tr, chip))
        print(f"idle {100 * idle * 1e-9 / tr.window_s:.3f} % of the window; "
              f"under program host work {100 * under * 1e-9 / tr.window_s:.3f}"
              f" % ({100 * under / max(idle, 1):.1f} % of the idle time)")


if __name__ == "__main__":
    main(sys.argv[1])
