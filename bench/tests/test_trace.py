"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``bench/tools/record_trace.py``): three rounds of a 2048² matmul, a wait
on the host, the paged-attention kernel and the WASH shuffle kernel."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import kernels, trace  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(DATA))


@pytest.fixture(scope="module")
def raw():
    """The trace's raw events, read without the reduction."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(DATA))
    ops, spans = [], []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                item = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append(item)
                elif plane.name == "/host:CPU" and ev.name.startswith("bench."):
                    spans.append(item)
    return ops, spans


def test_window_and_spans(tr, raw):
    _, spans = raw
    (w,) = [s for s in spans if s[0] == "bench.window"]
    assert tr.window == (w[1], w[2])
    waits = sum(e - s for n, s, e in spans if n == "bench.host_wait")
    assert tr.span_seconds()["bench.host_wait"] == pytest.approx(waits * 1e-9)
    assert tr.chips == [0]


def test_busy_time_against_a_raster(tr, raw):
    ops, _ = raw
    lo, hi = tr.window
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)   # 10 ns bins
    for _, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[int((s - lo) // 10):int((e - lo) // 10)] = True
    assert tr.busy_s() == pytest.approx(grid.sum() * 10e-9, rel=1e-3)
    assert 0 < tr.busy_s() < tr.window_s


def test_kernels_are_found_by_name(tr, raw):
    ops, _ = raw
    for match, prefix in ((kernels.paged_attention, "%paged_attention_pallas"),
                          (kernels.wash_shuffle, "%bucketed_shuffle_pallas")):
        events = [(s, e) for n, s, e in ops if n.startswith(prefix)]
        assert len(events) == 3
        assert tr.op_count(match) == 3
        assert tr.op_seconds(match) == pytest.approx(
            sum(e - s for s, e in events) * 1e-9)
    sorts = [(s, e) for n, s, e in ops if n.startswith("%sort ")]
    assert len(sorts) == 3


def test_top_ops_name_program_and_op(tr):
    top = tr.top_ops(3)
    assert top[0][0].startswith("jit__lambda#") and top[0][0].endswith("/fusion")
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)


def test_longest_gaps_fall_in_the_host_wait(tr):
    gaps = tr.idle_gaps(3)
    assert [name for name, _ in gaps] == ["bench.host_wait"] * 3
    assert all(s > 1e-3 for _, s in gaps)
    # every gap, and the busy time, tile the window
    busy = tr.busy_s()
    idle = sum(s for _, s in tr.idle_gaps(10_000))
    assert busy + idle == pytest.approx(tr.window_s, rel=1e-9)
