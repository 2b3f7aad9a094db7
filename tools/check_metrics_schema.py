#!/usr/bin/env python3
"""Validate telemetry JSONL event streams against the documented schema.

Stdlib-only (CI runs it on raw launcher output, no jax import).  Checks,
per ``docs/OBSERVABILITY.md``:

  * every line is one JSON object with a ``kind`` from the known set;
  * line 1 is the provenance record (jax version, backend, device kind,
    device count, platform, timestamps);
  * every non-provenance record has a ``name`` matching
    ``[a-z0-9_.]+`` (dot-separated lowercase) and a float ``ts``;
  * spans carry ``dur_s >= 0``, an integer ``t0_ns >= 0`` (the start on
    the profiler's host clock) and ``parent``, the enclosing span's name
    or null;
  * metric snapshot lines are internally consistent — histograms have
    ``len(counts) == len(edges) + 1`` and ``sum(counts) == count``,
    counters are non-negative;
  * ``train.comm_volume`` events replay exactly: re-running the same
    float64 adds (``mix_steps`` additions of ``comm_per_mix_step``, in
    stream order) must reproduce each event's cumulative ``comm_total``
    bit-for-bit — the checker-side mirror of the engines' exact
    host-side WASH comm accounting.

Usage::

    python tools/check_metrics_schema.py out.jsonl [more.jsonl ...]
    python tools/check_metrics_schema.py --require-comm train.jsonl

``--require-comm`` additionally fails streams containing NO comm-volume
events (the CI train smoke must produce them).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _cli  # noqa: E402

KINDS = {"provenance", "span", "event", "compile", "metric"}
NAME_RE = re.compile(r"^[a-z0-9_.]+$")
PROVENANCE_FIELDS = ("ts", "timestamp", "jax_version", "backend",
                     "device_kind", "device_count", "platform")


def check_stream(path: str, require_comm: bool = False) -> List[str]:
    """Return a list of violation messages (empty = valid)."""
    errors: List[str] = []

    def err(lineno: int, msg: str) -> None:
        errors.append(f"{path}:{lineno}: {msg}")

    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError as e:
        return [f"{path}: unreadable: {e}"]
    if not lines:
        return [f"{path}: empty stream (expected a provenance line)"]

    comm_replay = 0.0
    comm_events = 0
    counters_seen: Dict[str, float] = {}

    for i, line in enumerate(lines, 1):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            err(i, f"not valid JSON: {e}")
            continue
        if not isinstance(rec, dict):
            err(i, f"expected a JSON object, got {type(rec).__name__}")
            continue
        kind = rec.get("kind")
        if kind not in KINDS:
            err(i, f"unknown kind {kind!r} (expected one of {sorted(KINDS)})")
            continue

        if i == 1:
            if kind != "provenance":
                err(i, f"first record must be provenance, got {kind!r}")
            continue
        if kind == "provenance":
            if i != 1:
                err(i, "provenance must be the first record only")
            continue

        name = rec.get("name")
        if not isinstance(name, str) or not NAME_RE.match(name):
            err(i, f"bad metric/event name {name!r} "
                   f"(expected lowercase dotted [a-z0-9_.]+)")
        ts = rec.get("ts")
        if not isinstance(ts, (int, float)):
            err(i, f"missing/non-numeric ts: {ts!r}")

        if kind == "span":
            dur = rec.get("dur_s")
            if not isinstance(dur, (int, float)) or dur < 0:
                err(i, f"span needs dur_s >= 0, got {dur!r}")
            t0 = rec.get("t0_ns")
            if not isinstance(t0, int) or isinstance(t0, bool) or t0 < 0:
                err(i, f"span needs an integer t0_ns >= 0, got {t0!r}")
            parent = rec.get("parent", "")
            if parent is not None and not (isinstance(parent, str)
                                           and NAME_RE.match(parent)):
                err(i, f"span parent must be a span name or null, got "
                       f"{parent!r}")
        elif kind == "event" and name == "train.comm_volume":
            per = rec.get("comm_per_mix_step")
            steps = rec.get("mix_steps")
            total = rec.get("comm_total")
            if (not isinstance(per, (int, float))
                    or not isinstance(steps, int) or steps < 1
                    or not isinstance(total, (int, float))):
                err(i, "comm_volume event needs float comm_per_mix_step, "
                       "int mix_steps >= 1, float comm_total")
            else:
                # replay the engine's exact accumulation: same adds, same
                # order, starting from zero — must match bit-for-bit
                for _ in range(steps):
                    comm_replay += float(per)
                comm_events += 1
                if comm_replay != float(total):
                    err(i, f"comm_volume replay mismatch: engine total "
                           f"{total!r} vs replayed {comm_replay!r}")
        elif kind == "metric":
            mtype = rec.get("type")
            if mtype == "histogram":
                edges = rec.get("edges")
                counts = rec.get("counts")
                count = rec.get("count")
                if (not isinstance(edges, list) or not isinstance(counts, list)
                        or len(counts) != len(edges) + 1):
                    err(i, "histogram needs len(counts) == len(edges) + 1")
                elif sum(counts) != count:
                    err(i, f"histogram counts sum {sum(counts)} != "
                           f"count {count}")
                elif any(b <= a for a, b in zip(edges, edges[1:])):
                    err(i, "histogram edges must be strictly increasing")
            elif mtype == "counter":
                v = rec.get("value")
                if not isinstance(v, (int, float)) or v < 0:
                    err(i, f"counter value must be >= 0, got {v!r}")
                prev = counters_seen.get(name)
                if prev is not None and v < prev:
                    err(i, f"counter {name} went backwards "
                           f"({prev} -> {v})")
                counters_seen[name] = v
            elif mtype != "gauge":
                err(i, f"unknown metric type {mtype!r}")

    if require_comm and comm_events == 0 and not errors:
        errors.append(
            f"{path}: --require-comm: no train.comm_volume events found")
    return errors


def build_parser() -> argparse.ArgumentParser:
    ap = _cli.make_parser(__doc__)
    ap.add_argument("paths", nargs="+", help="JSONL event streams to check")
    ap.add_argument("--require-comm", action="store_true",
                    help="fail streams with no train.comm_volume events")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    failed = False
    for path in args.paths:
        errors = check_stream(path, require_comm=args.require_comm)
        if errors:
            failed = True
            for e in errors:
                print(e, file=sys.stderr)
        else:
            with open(path) as f:
                n = sum(1 for _ in f)
            print(f"{path}: OK ({n} records)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
