"""A cell, a configuration, a traffic mix and a per-layer metric are
found by name from files a later change adds, with no existing file
edited."""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import run as RUN  # noqa: E402
from bench.tests import tiny  # noqa: E402


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    root = tiny.write(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a later change adds files for a new configuration, mix and cell ...
    (root / "bench/configs/tiny2.json").write_text(
        json.dumps(dict(tiny.TINY_CONFIG, num_hidden_layers=3)))
    (root / "bench/traffic/serve.burst.json").write_text(
        json.dumps(dict(tiny.SERVE_TRAFFIC, rate=80.0)))
    (root / "bench/workloads/tiny2.serve.burst.json").write_text(
        json.dumps(tiny.SERVE_WORKLOAD))
    (root / "bench/metrics/new_metric.online.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.counters['x']\n")
    # ... and entries in BENCHMARK.json
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "bench/configs/tiny2.json",
                            "reduced": []})
    spec["workloads"].append({"name": "tiny2.serve.burst", "config": "tiny2",
                              "traffic": "serve.burst", "chips": 1})
    spec["per_layer"].append({"name": "new_metric.online", "unit": "ms",
                              "workloads": ["tiny2.serve.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = H.find_cell("tiny2.serve.burst", root)
    assert cell.config["num_hidden_layers"] == 3
    assert cell.traffic["rate"] == 80.0
    assert [m["name"] for m in cell.per_layer] == ["new_metric.online"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    read = RUN.load_reader("new_metric.online", root)
    assert read(type("ctx", (), {"counters": {"x": 1.5}})) == 3.0
    # the cells already there keep their own metrics
    assert "new_metric.online" not in {
        m["name"] for m in H.find_cell("tiny.serve", root).per_layer}


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(RUN.load_reader(m["name"])), m["name"]
    for w in spec["workloads"]:
        cell = H.find_cell(w["name"])
        assert cell.traffic["driver"] in ("train", "serve")
        assert "limits" in cell.workload


def test_a_family_reader_serves_each_split_name(tmp_path):
    """``idle_share.py`` reads ``idle_share.train`` and any later split of
    it; a reader of the full name comes first."""
    root = tiny.write(tmp_path)
    ctx = type("ctx", (), {"trace": None})
    assert RUN.load_reader("idle_share.later_cell", root)(ctx) is None
    (root / "bench/metrics/idle_share.later_cell.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    assert RUN.load_reader("idle_share.later_cell", root)(ctx) == 1.0
