"""Reduction of a profiler trace to device busy time, op and kernel time,
host-span time, and the longest idle gaps by host span.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Each TPU chip
is a plane ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one event per
executed HLO op, named by the op's HLO text (``%fusion.3 = bf16[...]
fusion(...)``); its line ``XLA Modules`` one event per program run
(``jit_program(<hash>)``).  A Pallas kernel is a ``custom-call`` op with
``custom_call_target="tpu_custom_call"``.  The benchmark's own host spans
are TraceAnnotations named ``bench.<what>`` on the host plane
``/host:CPU``; ``bench.window`` bounds the measured window.  Every time is
clipped to that window.  The host's and the chip's clocks in one trace
can differ by about a millisecond (1 ms on the recorded test trace).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Op:
    text: str    # the op's HLO text
    start: int   # ns
    end: int     # ns
    module: str  # the program it ran in, e.g. ``jit_program#4417``

    @property
    def name(self) -> str:
        """The op's HLO name: ``fusion.3`` of ``%fusion.3 = ...``."""
        head = self.text.split(" = ", 1)[0]
        return head[1:] if head.startswith("%") else head


def _module_name(event_name: str) -> str:
    """``jit_program(1234567...)`` -> ``jit_program#1234``."""
    base, _, rest = event_name.partition("(")
    return f"{base}#{rest[:4]}" if rest else base


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]                    # chip -> ops, by start
    spans: List[Tuple[str, int, int]]           # host spans (name, s, e)
    window: Tuple[int, int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def _clip(self, s: int, e: int) -> int:
        return max(0, min(e, self.window[1]) - max(s, self.window[0]))

    def busy_intervals(self, chip: int) -> List[Tuple[int, int]]:
        """Union of the chip's op intervals inside the window."""
        lo, hi = self.window
        out: List[List[int]] = []
        for op in self.ops.get(chip, []):
            s, e = max(op.start, lo), min(op.end, hi)
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [(s, e) for s, e in out]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        tot = sum(sum(e - s for s, e in self.busy_intervals(c))
                  for c in self.chips)
        return tot * 1e-9 / len(self.chips)

    def op_seconds(self, match: Callable[[Op], bool]) -> float:
        """Seconds of the ops ``match`` selects, averaged over the chips."""
        if not self.ops:
            return 0.0
        tot = sum(self._clip(op.start, op.end)
                  for c in self.chips for op in self.ops[c] if match(op))
        return tot * 1e-9 / len(self.chips)

    def op_count(self, match: Callable[[Op], bool]) -> int:
        return sum(1 for c in self.chips for op in self.ops[c]
                   if match(op) and self._clip(op.start, op.end) > 0)

    def self_times(self, chip: int) -> List[Tuple[Op, int]]:
        """Each op with its own time in the window: an op that holds
        others (a ``while`` loop holds its body's ops) keeps only the time
        none of them covers."""
        out: List[List] = []
        stack: List[List] = []
        for op in sorted(self.ops.get(chip, []),
                         key=lambda o: (o.start, -o.end)):
            while stack and stack[-1][0].end <= op.start:
                stack.pop()
            own = [op, self._clip(op.start, op.end)]
            if stack:
                stack[-1][1] -= own[1]
            stack.append(own)
            out.append(own)
        return [(op, max(t, 0)) for op, t in out]

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` ops (``<program>/<op>``) whose own time (see
        ``self_times``) was largest, averaged over the chips."""
        tot: Dict[str, int] = {}
        for c in self.chips:
            for op, t in self.self_times(c):
                key = f"{op.module}/{op.name}"
                tot[key] = tot.get(key, 0) + t
        n = max(len(self.chips), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [(name, ns * 1e-9 / n) for name, ns in ranked]

    def span_seconds(self) -> Dict[str, float]:
        """Host seconds under each of the benchmark's spans."""
        out: Dict[str, float] = {}
        for name, s, e in self.spans:
            out[name] = out.get(name, 0.0) + self._clip(s, e) * 1e-9
        return out

    def idle_gaps(self, k: int = 10, chip: Optional[int] = None
                  ) -> List[Tuple[str, float]]:
        """The ``k`` longest idle gaps of a chip inside the window, each
        named by the host span (other than the window) that overlaps it
        most, or ``idle`` where none does."""
        if not self.ops:
            return []
        chip = self.chips[0] if chip is None else chip
        busy = self.busy_intervals(chip)
        lo, hi = self.window
        gaps, prev = [], lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if hi > prev:
            gaps.append((prev, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = [sp for sp in self.spans if sp[0] != WINDOW]
        out = []
        for s, e in gaps[:k]:
            best, most = "idle", 0
            for name, a, b in inner:
                ov = min(b, e) - max(a, s)
                if ov > most:
                    best, most = name, ov
            out.append((best, (e - s) * 1e-9))
        return out


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` file (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops: Dict[int, List[Op]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.end_ns),
                           _module_name(ev.name))
                          for ev in (lines[MODULES_LINE].events
                                     if MODULES_LINE in lines else ()))
            chip_ops, j = [], 0
            for ev in sorted(lines[OPS_LINE].events if OPS_LINE in lines
                             else (), key=lambda e: e.start_ns):
                s_, e_ = int(ev.start_ns), int(ev.end_ns)
                while j < len(mods) and mods[j][1] < s_:
                    j += 1
                mod = mods[j][2] if j < len(mods) and mods[j][0] <= s_ else ""
                chip_ops.append(Op(ev.name, s_, e_, mod))
            ops[chip] = chip_ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.end_ns)))
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} span")
    return Trace(ops=ops, spans=spans, window=max(windows,
                                                  key=lambda w: w[1] - w[0]))
