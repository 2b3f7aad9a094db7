"""A cell, a configuration, an architecture, a traffic mix and a
per-layer metric are found by name from files a later change adds, with
no existing file edited."""

import json
import pathlib
import shutil
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import run as RUN  # noqa: E402
from bench.tests import tiny  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"


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


def test_an_architecture_comes_in_as_files(tmp_path):
    """A model unlike the dense one enters as files: ``tiny_moe``
    (``bench/tests/data/tiny_moe.py``), whose blocks route each token to
    2 of 4 SwiGLU experts, a configuration that names it and a serve and
    a train cell.  Both run ``correct`` through ``bench/run.py``'s entry
    on the CPU; no file that was there changes but ``BENCHMARK.json``,
    which only gains entries."""
    root = tiny.write(tmp_path)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())

    shutil.copy(DATA / "tiny_moe.py", root / "bench/architectures")
    (root / "bench/configs/tiny_moe.json").write_text(json.dumps(dict(
        tiny.TINY_CONFIG, architecture="tiny_moe", n_routed_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=32)))
    (root / "bench/workloads/tiny_moe.serve.json").write_text(
        json.dumps(tiny.SERVE_WORKLOAD))
    (root / "bench/workloads/tiny_moe.train.json").write_text(
        json.dumps(tiny.TRAIN_WORKLOAD))
    new = json.loads(json.dumps(spec))
    new["configs"].append({"name": "tiny_moe", "source": "test",
                           "file": "bench/configs/tiny_moe.json",
                           "reduced": []})
    new["workloads"] += [
        {"name": "tiny_moe.serve", "config": "tiny_moe",
         "traffic": "serve.tiny", "chips": 1},
        {"name": "tiny_moe.train", "config": "tiny_moe",
         "traffic": "train.tiny", "chips": 1}]
    for m in new["end_to_end"]:
        if m["name"] in ("train_tokens_per_s", "serve_tokens_per_s"):
            m["workloads"].append(f"tiny_moe.{m['name'].split('_')[0]}")
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    cell = H.find_cell("tiny_moe.serve", root)
    assert cell.architecture.__file__ == str(
        (root / "bench/architectures/tiny_moe.py").resolve())
    a = cell.architecture.Arch.from_config(cell.config)
    cfg = cell.architecture.program_config(a, "tiny_moe")
    assert cfg.moe and cfg.capacity_factor * cfg.top_k == cfg.n_routed_experts
    for name in ("tiny_moe.serve", "tiny_moe.train"):
        args = RUN.parse(["--workload", name, "--seed", "3000000021",
                          "--seconds", "2", "--trace", "0"])
        result, checks = RUN.run(args, root=root, check_device=False,
                                 peak=tiny.PEAK, t_start=time.perf_counter())
        assert result["correct"], (name, checks)
        assert result["attempted"] > 0

    after = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {root / "BENCHMARK.json"}
    grown = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert grown[key][:len(spec[key])] == spec[key]


def test_a_configuration_must_name_its_architecture(tmp_path):
    config = {k: v for k, v in tiny.TINY_CONFIG.items()
              if k != "architecture"}
    root = tiny.write(tmp_path, config=config)
    with pytest.raises(H.BenchError, match="tiny.json"):
        H.find_cell("tiny.serve", root)
