"""Summarize the device ops of a profiler trace, to find how the trace
names a kernel or an op before writing a metric reader against it.

    python3 bench/tools/trace_ops.py bench_out/trace/<cell>.<seed>

Prints the window, busy time, the ops with the most own time, and the
HLO text of every custom call (each distinct name once).
"""

from __future__ import annotations

import pathlib
import re
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402


def main(path: str) -> None:
    tr = trace.load(path)
    print(f"window {tr.window_s:.6f} s, busy {tr.busy_s():.6f} s, "
          f"chips {tr.chips}")
    kinds = {}
    for op, t in tr.self_times(tr.chips[0]):
        base = re.sub(r"\.\d+$", "", op.name)
        n, s = kinds.get(base, (0, 0))
        kinds[base] = (n + 1, s + t)
    for base, (n, s) in sorted(kinds.items(), key=lambda kv: -kv[1][1])[:40]:
        print(f"{s * 1e-9:12.6f} s {n:8d}  {base}")
    seen = set()
    for op in tr.ops[tr.chips[0]]:
        if "custom-call" in op.text and op.name not in seen:
            seen.add(op.name)
            print("CUSTOM", op.module, op.text[:600])


if __name__ == "__main__":
    main(sys.argv[1])
