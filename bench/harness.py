"""What every cell shares: finding its files by name, the device, compile
events, host spans, the profiler window and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import math
import pathlib
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: profiler traces of --trace 1 runs
TRACE_DIR = ROOT / "bench_out" / "trace"


class BenchError(RuntimeError):
    """The run cannot be made as asked (missing file, no chip, ...)."""


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with the files it names."""

    name: str
    entry: Dict
    config: Dict
    traffic: Dict
    workload: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` and its files:
    ``bench/workloads/<cell>.json``, the configuration's ``file`` and
    ``bench/traffic/<traffic>.json``."""
    spec_path = root / "BENCHMARK.json"
    if not spec_path.exists():
        raise BenchError(f"{spec_path} not found")
    spec = load_json(spec_path)
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    return Cell(
        name=name, entry=entry,
        config=load_json(root / configs[entry["config"]]["file"]),
        traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
        workload=load_json(bench / "workloads" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def driver_module(kind: str):
    """``bench/drivers/<kind>.py``: one driver per kind of traffic."""
    return importlib.import_module(f"bench.drivers.{kind}")


# ---------------------------------------------------------------------------
# device, compile cache, compile events
# ---------------------------------------------------------------------------


def require_chips(n: int) -> Dict:
    """The device as JAX reports it; raise unless it holds ``n`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devices)}")
    return device_info(n)


def device_info(n: int) -> Dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n}


def memory_peak_bytes(n: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def enable_compile_cache() -> str:
    """JAX's persistent cache at the fixed ``.jax_cache/`` of the checkout,
    so that only a cell's first run in a checkout compiles.  Every program
    is kept, however short its compile, and nothing is evicted: a size cap
    from the environment would drop a cell's large programs between runs."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileEvents:
    """Counts programs built (compiled or loaded from the persistent
    cache) from JAX's monitoring events."""

    BUILD = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.builds = 0
        self.hits = 0
        self.misses = 0
        self.build_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == self.BUILD:
            self.builds += 1
            self.build_s += secs


# ---------------------------------------------------------------------------
# host spans and the profiler window
# ---------------------------------------------------------------------------


class Spans:
    """The benchmark's own host spans: each is timed on the host clock
    and, while a trace is open, written into it as a TraceAnnotation."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.times: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        ann = jax.profiler.TraceAnnotation(name) if self.traced else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.times.setdefault(name, []).append(time.perf_counter() - t0)
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, obj: Any, attr: str, name: str) -> None:
        """Time every call of ``obj.attr`` as span ``name``."""
        fn = getattr(obj, attr)

        def timed(*a, **kw):
            with self(name):
                return fn(*a, **kw)

        setattr(obj, attr, timed)


@contextlib.contextmanager
def profile_window(traced: bool, logdir: pathlib.Path):
    """The profiler trace around a measured window, on the program's own
    capture window (:class:`repro.obs.profiler.ProfileWindow`)."""
    if not traced:
        yield
        return
    from repro.obs.profiler import ProfileWindow

    logdir.mkdir(parents=True, exist_ok=True)
    window = ProfileWindow(str(logdir), max_spans=1 << 30)
    try:
        yield
    finally:
        window.stop()


# ---------------------------------------------------------------------------
# statistics and output
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> Optional[float]:
    """Exact linear-interpolation percentile (numpy's default), None when
    empty."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def median(values) -> Optional[float]:
    vals = list(values)
    return statistics.median(vals) if vals else None


def emit(result: Dict, checks: List[Tuple[str, float, float]]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line as the last line of standard
    output (the checks again, under the key that comes last)."""
    for name, value, limit in checks:
        ok = "ok" if value <= limit else "FAIL"
        print(f"check {name}: {value!r} (limit {limit!r}) {ok}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
