"""The program's own names in a profiler trace of a window: the scope of
each device op, and the program's host spans.

``bench/trace.py`` reduces a trace to what the benchmark can see from
outside the program: device ops by their HLO names and the benchmark's
own ``bench.*`` spans.  The program names its work too.  Its telemetry
spans (``repro.obs``) are TraceMe events on the host plane named
``serve.<what>`` or ``train.<what>``, nested as the calls nest (a
``serve.tick`` holds ``serve.decode_step``, which holds
``serve.decode_wait``); its ``jax.named_scope``s sit in each device op's
``tf_op`` stat (``.../wash/plan/...``), which ``bench/xplane.py`` reads.

A reader gets the window's :class:`bench.trace.Trace`; :func:`for_trace`
finds the trace file whose ``bench.window`` span that is, under the
checkout's trace directory, and reads both.  A program that names nothing
(no program span, no scope) gives nothing to read, and each reader then
returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import pathlib
import re
from typing import Callable, Dict, List, Optional, Tuple

from bench import xplane

HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
PROGRAM_PREFIXES = ("serve.", "train.")
#: the trace directory of a checkout, as ``bench/run.py`` writes it
TRACE_DIR = pathlib.Path("bench_out") / "trace"

_WRAPPED = re.compile(r"^(?:[\w.-]+\()*([^()]*)\)*$")


def segments(scope: str) -> List[str]:
    """A scope path's names, each without the transforms JAX wraps around
    it: ``jit(f)/vmap(jvp(loss))/mlp`` -> ``['f', 'loss', 'mlp']``."""
    out = []
    for seg in scope.split("/"):
        m = _WRAPPED.match(seg)
        out.append(m.group(1) if m else seg)
    return out


@dataclasses.dataclass
class ProgramTrace:
    #: chip -> op name -> what each program's metadata records for it
    ops: Dict[int, Dict[str, List[xplane.OpInfo]]]
    #: the program's host spans ``(name, start, end)`` in ns, clipped to
    #: the window, by start
    spans: List[Tuple[str, int, int]]

    def scope(self, chip: int, op) -> str:
        """The scope path of a :class:`bench.trace.Op` (``""`` where its
        metadata has none).  An op name that several programs use is told
        apart by the program id the op's module carries."""
        infos = self.ops.get(chip, {}).get(op.text, [])
        if len(infos) > 1:
            prefix = op.module.partition("#")[2]
            infos = [i for i in infos
                     if str(i.program_id).startswith(prefix)] or infos
        return xplane.scope_of(infos[0].tf_op) if infos else ""

    def own_seconds(self, trace, match: Callable[[str], bool]) -> float:
        """Own time (as ``Trace.top_ops`` counts it) of the ops whose scope
        ``match`` selects, averaged over the chips."""
        if not trace.ops:
            return 0.0
        tot = sum(t for c in trace.chips for op, t in trace.self_times(c)
                  if match(self.scope(c, op)))
        return tot * 1e-9 / len(trace.chips)

    def has_scope(self, name: str) -> bool:
        """Whether any device op's scope path holds ``name``."""
        return any(name in segments(xplane.scope_of(i.tf_op))
                   for ops in self.ops.values() for infos in ops.values()
                   for i in infos)

    def named(self, name: str) -> List[Tuple[int, int]]:
        return [(s, e) for n, s, e in self.spans if n == name]


def innermost(spans: List[Tuple[str, int, int]]
              ) -> List[Tuple[int, int, str]]:
    """The time covered by ``spans`` cut into pieces ``(start, end, name)``,
    each named by its innermost open span: the open one that started
    last, which is the innermost where spans nest."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    starts: Dict[int, List[int]] = {}
    ends: Dict[int, List[int]] = {}
    for i, (_, s, e) in enumerate(spans):
        if e > s:
            starts.setdefault(s, []).append(i)
            ends.setdefault(e, []).append(i)
    open_: List[int] = []
    out: List[Tuple[int, int, str]] = []
    for a, b in zip(bounds, bounds[1:]):
        for i in ends.get(a, ()):
            open_.remove(i)
        open_.extend(sorted(starts.get(a, ()),
                            key=lambda i: (-spans[i][2], i)))
        if open_:
            name = spans[open_[-1]][0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def overlap(pieces: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """ns of ``[lo, hi)`` covered by ``pieces`` (disjoint, by start)."""
    i = max(bisect.bisect_right(pieces, (lo, lo)) - 1, 0)
    tot = 0
    while i < len(pieces) and pieces[i][0] < hi:
        s, e = pieces[i]
        tot += max(0, min(e, hi) - max(s, lo))
        i += 1
    return tot


def idle_intervals(trace, chip: int) -> List[Tuple[int, int]]:
    """The chip's idle time inside the window, as disjoint intervals."""
    lo, hi = trace.window
    out, prev = [], lo
    for s, e in trace.busy_intervals(chip):
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        out.append((prev, hi))
    return out


def _host_spans(data, window: Tuple[int, int]):
    """The window span found in ``data`` and the program's host spans."""
    lo, hi = window
    spans, windows = [], []
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW:
                    windows.append((int(ev.start_ns), int(ev.end_ns)))
                elif name.startswith(PROGRAM_PREFIXES):
                    s, e = int(ev.start_ns), int(ev.end_ns)
                    if min(e, hi) > max(s, lo):
                        spans.append((name, max(s, lo), min(e, hi)))
    spans.sort(key=lambda sp: (sp[1], -sp[2]))
    return windows, spans


def load(path: str, window: Tuple[int, int]) -> Optional[ProgramTrace]:
    """The program's names in the trace file ``path``, or None when its
    ``bench.window`` span is not ``window``."""
    from jax.profiler import ProfileData

    windows, spans = _host_spans(ProfileData.from_file(path), window)
    if window not in windows:
        return None
    return ProgramTrace(ops=xplane.op_scopes(path), spans=spans)


_CACHE: Dict[Tuple[str, Tuple[int, int]], ProgramTrace] = {}


def for_trace(trace, root: pathlib.Path) -> Optional[ProgramTrace]:
    """The program's names in the trace of ``trace``'s window, searched
    for among the trace files under ``root``, newest first; None where
    none is found."""
    key = (str(root), trace.window)
    if key not in _CACHE:
        paths = glob.glob(str(root / TRACE_DIR / "**" / "*.xplane.pb"),
                          recursive=True)
        for path in sorted(paths, key=os.path.getmtime, reverse=True):
            found = load(path, trace.window)
            if found is not None:
                _CACHE[key] = found
                break
        else:
            return None
    return _CACHE[key]


def checkout_root(reader_file: str) -> pathlib.Path:
    """The checkout a reader ``bench/metrics/<name>.py`` belongs to."""
    return pathlib.Path(reader_file).resolve().parents[2]
