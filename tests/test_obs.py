"""Unit + property tests for the telemetry primitives (repro.obs).

The hypothesis layer (skipped when hypothesis isn't installed — it is a
dev-only dependency) explores the sample space for the histogram /
percentile invariants; the fixed-seed tests below pin the same
invariants on handcrafted inputs so CI without hypothesis still
exercises every branch.  The invariants:

  * merge is associative on everything percentiles read (counts, count,
    min, max — ``sum`` only to float rounding);
  * p50 <= p99 <= observed max, and every bucket percentile upper-bounds
    the exact sample percentile;
  * the exact (raw-sample) percentile matches numpy's default linear
    interpolation and guards the degenerate shapes summarize() hits.
"""

import json

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Histogram, Registry

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

EDGES = (0.001, 0.01, 0.1, 1.0, 10.0)


def _hist(values, name="h"):
    h = Histogram(name, EDGES)
    for v in values:
        h.observe(v)
    return h


# ---------------------------------------------------------------------------
# fixed-seed invariants (always run)
# ---------------------------------------------------------------------------


def _check_merge_associative(a, b, c):
    ab_c = _hist(a).merge(_hist(b)).merge(_hist(c))
    a_bc = _hist(a).merge(_hist(b).merge(_hist(c)))
    assert ab_c.counts == a_bc.counts
    assert ab_c.count == a_bc.count == len(a) + len(b) + len(c)
    assert ab_c.min == a_bc.min and ab_c.max == a_bc.max
    assert ab_c.sum == pytest.approx(a_bc.sum, rel=1e-12, abs=1e-15)
    # merged percentiles equal observing everything into one histogram
    one = _hist(list(a) + list(b) + list(c))
    for q in (0, 50, 90, 99, 100):
        assert ab_c.percentile(q) == one.percentile(q)


def _check_percentile_bounds(values):
    h = _hist(values)
    if not values:
        assert h.percentile(50) is None
        return
    p50, p99 = h.percentile(50), h.percentile(99)
    assert p50 <= p99 <= h.max
    ordered = sorted(values)
    for q in (10, 50, 90, 99):
        # the bucket estimate upper-bounds the nearest-rank percentile
        # (the rank-th order statistic) and never exceeds the observed max
        rank = max(1, min(len(ordered), -(-q * len(ordered) // 100)))
        assert ordered[int(rank) - 1] <= h.percentile(q) <= h.max
    # single sample: every q answers with that sample's bucket value
    h1 = _hist([values[0]])
    assert h1.percentile(0) == h1.percentile(50) == h1.percentile(100)


def test_merge_associative_fixed():
    rng = np.random.default_rng(0)
    for _ in range(10):
        parts = [rng.exponential(0.05, rng.integers(0, 30)).tolist()
                 for _ in range(3)]
        _check_merge_associative(*parts)
    _check_merge_associative([], [], [])          # all-empty merge
    _check_merge_associative([5.0], [], [1e9])    # overflow bucket


def test_percentile_bounds_fixed():
    rng = np.random.default_rng(1)
    for _ in range(10):
        _check_percentile_bounds(
            rng.exponential(0.05, rng.integers(1, 50)).tolist())
    _check_percentile_bounds([])
    _check_percentile_bounds([1e9, 2e9])          # overflow-only: max wins
    h = _hist([1e9, 2e9])
    assert h.percentile(99) == 2e9


if HAVE_HYPOTHESIS:
    class TestHypothesis:
        @settings(max_examples=50, deadline=None)
        @given(st.lists(st.floats(0, 100, allow_nan=False), max_size=30),
               st.lists(st.floats(0, 100, allow_nan=False), max_size=30),
               st.lists(st.floats(0, 100, allow_nan=False), max_size=30))
        def test_merge_associative(self, a, b, c):
            _check_merge_associative(a, b, c)

        @settings(max_examples=50, deadline=None)
        @given(st.lists(st.floats(1e-6, 1e6, allow_nan=False), min_size=1,
                        max_size=50))
        def test_percentile_bounds(self, values):
            _check_percentile_bounds(values)

        @settings(max_examples=50, deadline=None)
        @given(st.lists(st.floats(-1e6, 1e6, allow_nan=False), min_size=2,
                        max_size=50),
               st.floats(0, 100))
        def test_exact_percentile_matches_numpy(self, values, q):
            assert obs.percentile(values, q) == pytest.approx(
                float(np.percentile(values, q)), rel=1e-9, abs=1e-9)


# ---------------------------------------------------------------------------
# exact percentile: degenerate cases summarize() depends on
# ---------------------------------------------------------------------------


def test_exact_percentile_degenerate():
    assert obs.percentile([], 50) is None
    assert obs.percentile([None, None], 99) is None
    assert obs.percentile([0.25], 0) == 0.25          # single sample
    assert obs.percentile([0.25], 99) == 0.25
    assert obs.percentile([None, 0.5, None, 0.1], 0) == 0.1
    assert obs.percentile_ms([0.5], 50) == 500.0
    vals = [0.3, 0.1, 0.9, 0.5, 0.2]
    for q in (0, 25, 50, 75, 99, 100):
        assert obs.percentile(vals, q) == pytest.approx(
            float(np.percentile(vals, q)), abs=1e-12)
    with pytest.raises(ValueError):
        obs.percentile([1.0], 101)


def test_summarize_samples():
    assert obs.summarize_samples([])["count"] == 0
    s = obs.summarize_samples([0.1, None, 0.3])
    assert s["count"] == 2 and s["min"] == 0.1 and s["max"] == 0.3


def test_driver_summarize_degenerate():
    """The migrated serving summary survives every fragile shape."""
    from repro.serving.driver import RequestMetrics, summarize

    # empty metrics dict
    s = summarize({})
    assert s["requests"] == 0 and s["ttft_p99_ms"] is None
    assert s["tokens_per_s"] is None
    # all-cancelled
    m = RequestMetrics(uid=0, arrival=0.0)
    m.cancelled, m.finished = True, 1.0
    s = summarize({0: m})
    assert s["requests"] == 0 and s["cancelled"] == 1
    # single request, zero generated tokens, no first token
    m2 = RequestMetrics(uid=1, arrival=0.0)
    m2.finished = 2.0
    s = summarize({1: m2})
    assert s["requests"] == 1
    assert s["ttft_p50_ms"] is None                  # no first token
    assert s["intertoken_p99_ms"] is None            # zero-token request
    assert s["latency_p99_ms"] == pytest.approx(2000.0)  # single-sample p99
    # one token: no gaps, but a TTFT
    m3 = RequestMetrics(uid=2, arrival=0.0)
    m3.first_token, m3.finished = 0.5, 1.0
    m3.token_times = [0.5]
    s = summarize({2: m3})
    assert s["ttft_p50_ms"] == pytest.approx(500.0)
    assert s["intertoken_p99_ms"] is None


# ---------------------------------------------------------------------------
# registry, sinks, counters, gauges
# ---------------------------------------------------------------------------


def test_registry_accessors_and_conflicts():
    r = Registry()
    c = r.counter("a.count")
    assert r.counter("a.count") is c
    c.inc(); c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        r.gauge("a.count")  # type conflict
    r.gauge("g").set(7)
    assert r.snapshot()["g"]["value"] == 7.0
    assert r.names() == ["a.count", "g"]
    r.reset()
    assert r.names() == []


def test_counter_mirrors_float_accumulation():
    """inc-per-step reproduces a += accumulation bit-for-bit — the
    property the train engines' comm mirror depends on."""
    r = Registry()
    c = r.counter("comm")
    per = 0.1  # not exactly representable: order matters
    total = 0.0
    for _ in range(1000):
        total += per
        c.inc(per)
    assert c.value == total  # bitwise, not approx


def test_histogram_validation():
    with pytest.raises(ValueError):
        Histogram("bad", ())
    with pytest.raises(ValueError):
        Histogram("bad", (1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", EDGES).percentile(101)
    with pytest.raises(ValueError):
        Histogram("a", (1.0,)).merge(Histogram("b", (2.0,)))


def test_telemetry_sinks_and_events(tmp_path):
    tel = obs.Telemetry()
    mem = obs.MemorySink()
    tel.add_sink(mem)
    path = str(tmp_path / "out.jsonl")
    tel.add_sink(obs.JsonlSink(path))
    with tel.span("x.span", step=3):
        pass
    tel.event("x.event", foo=1)
    tel.record_compile("x_kind", shape=4)
    assert tel.registry.counter("compile.x_kind").value == 1
    assert tel.registry.histogram("x.span").count == 1
    tel.finalize()
    records = [json.loads(l) for l in open(path)]
    assert records[0]["kind"] == "provenance"
    kinds = {r["kind"] for r in records}
    assert {"span", "event", "compile", "metric"} <= kinds
    assert mem.named("x.event")[0]["foo"] == 1
    # and the stream passes the CI checker
    import subprocess, sys
    proc = subprocess.run(
        [sys.executable, "tools/check_metrics_schema.py", path],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_disabled_telemetry_is_noop(tmp_path):
    tel = obs.Telemetry()
    mem = obs.MemorySink()
    tel.add_sink(mem)
    tel.enabled = False
    with tel.span("x"):
        pass
    tel.event("e")
    tel.record_compile("k")
    assert tel.registry.names() == []
    assert [r["kind"] for r in mem.records] == ["provenance"]


def test_configure_resets_default():
    tel = obs.configure(memory=True)
    assert tel is obs.get()
    tel.registry.counter("x").inc()
    obs.reset()
    assert obs.get().registry.names() == []
