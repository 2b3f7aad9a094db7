"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic and
engine settings are files found by name under ``bench/``.  The run makes
its weights and inputs from ``--seed``, warms every program its window
uses, measures for ``--seconds``, then checks what the window produced
against the plain reference.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window), ``device`` and, last, ``checks``: each
compared number with its limit.  Without a TPU, or with fewer chips than
the cell needs, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402


def load_reader(name: str, root=H.ROOT):
    """``read`` of ``bench/metrics/<name>.py``, or, where there is none, of
    the family's reader ``bench/metrics/<base>.py`` (``idle_share.py``
    serves ``idle_share.train`` and ``idle_share.offline``)."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, *, root=H.ROOT, check_device=True, peak=None, t_start=None):
    """One run; returns the result line's object (without ``checks``) and
    the checks.  ``root``, ``check_device=False`` and ``peak`` are for the
    tests, which drive a run of a tiny cell on the CPU and read its
    per-layer metrics against a stated peak."""
    t_start = T_START if t_start is None else t_start
    cell = H.find_cell(args.workload, root)
    from bench.peaks import peaks

    if check_device:
        device = H.require_chips(cell.chips)
        peak = peaks(device["kind"])
        H.enable_compile_cache()
    else:
        device = H.device_info(cell.chips)
    events = H.CompileEvents()
    traced = bool(args.trace)
    spans = H.Spans(traced)
    drv = H.driver_module(cell.traffic["driver"]).Driver(cell, args.seed,
                                                         spans)
    drv.setup(args.seconds)
    builds = events.builds
    setup_s = time.perf_counter() - t_start
    H.log(f"setup {setup_s:.3f} s; programs built {events.builds} "
          f"(cache hits {events.hits}, misses {events.misses}, "
          f"{events.build_s:.3f} s)")

    logdir = root / H.TRACE_DIR.relative_to(H.ROOT) / f"{args.workload}.{args.seed}"
    if traced:
        shutil.rmtree(logdir, ignore_errors=True)
    with H.profile_window(traced, logdir):
        with spans("bench.window"):
            drv.window(args.seconds)
    in_window = events.builds - builds
    H.log(f"programs built inside the window: {in_window}")
    device["memory_peak_bytes"] = H.memory_peak_bytes(cell.chips)

    metrics, breakdown = {}, None
    if traced:
        from bench import trace as TR

        tr = TR.load(str(logdir))
        ctx = types.SimpleNamespace(trace=tr, counters=drv.counters,
                                    peaks=peak, chips=cell.chips,
                                    arch=drv.arch)
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(10)],
                     "idle_gaps": [list(x) for x in tr.idle_gaps(10)]}
        H.log(f"host spans (s): {tr.span_seconds()}")
    else:
        values = dict(drv.end_to_end(cell.chips), setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    attempted = drv.counters.get("attempted", drv.counters.get("steps"))
    failed = drv.counters.get("failed", 0)

    drv.release()
    checks = drv.check() + [("window_programs", in_window, 0)]
    result = {
        "correct": all(v <= lim for _, v, lim in checks),
        "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks = run(args)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    H.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
