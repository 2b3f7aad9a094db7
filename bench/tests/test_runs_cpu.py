"""Runs of the tiny cells on the CPU through the harness's internal entry
(the command itself refuses a machine without a TPU): each driver's
set-up, window, reference check and result, and the faults the check
must catch, planted in the program underneath a run."""

import os
import pathlib
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as RUN  # noqa: E402
from bench.tests import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write(tmp_path_factory.mktemp("tiny"))


def one_run(root, cell, seconds=1.0, trace=0, seed=3000000007):
    args = RUN.parse(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)])
    return RUN.run(args, root=root, check_device=False, peak=tiny.PEAK,
                   t_start=time.perf_counter())


def test_train_run_is_correct(root):
    result, checks = one_run(root, "tiny.train")
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert dict((n, v) for n, v, _ in checks)["window_programs"] == 0


def test_train_traced_run_reports_per_layer(root):
    result, _ = one_run(root, "tiny.train", trace=1)
    assert "mfu.train" in result["metrics"]
    assert "setup_s" not in result["metrics"]
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_serve_run_is_correct(root):
    result, checks = one_run(root, "tiny.serve", seconds=2.0)
    assert result["correct"], checks
    assert result["attempted"] > 0
    assert {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
            "setup_s"} <= set(result["metrics"])


def test_backlog_run_starts_with_every_slot_decoding(root):
    result, checks = one_run(root, "tiny.backlog", seconds=2.0)
    assert result["correct"], checks
    assert result["attempted"] == tiny.BACKLOG_TRAFFIC["requests"]
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}


# -- faults planted in the program: each must make `correct` false -------


def _train_frozen(monkeypatch):
    """The chunk returns its state unchanged."""
    from repro.train import engine as T

    real = T.make_fused_chunk_fn

    def make(*a, **kw):
        kw["donate"] = False
        step = real(*a, **kw)
        return lambda p, o, *rest: (p, o, step(p, o, *rest)[2])

    monkeypatch.setattr(T, "make_fused_chunk_fn", make)


def _train_half_batch(monkeypatch):
    """Each member's loss over half its rows."""
    from repro.models import transformer as M

    real = M.loss_fn
    monkeypatch.setattr(M, "loss_fn", lambda p, cfg, b: real(
        p, cfg, {"tokens": b["tokens"][:b["tokens"].shape[0] // 2]}))


def _serve_frozen(monkeypatch):
    """The decode step returns the KV pools unchanged."""
    from repro.models import transformer as M

    real = M.decode_step_paged

    def step(params, cfg, tokens, positions, pools, *a, **kw):
        logits, _ = real(params, cfg, tokens, positions, pools, *a, **kw)
        return logits, pools

    monkeypatch.setattr(M, "decode_step_paged", step)


def _serve_half_batch(monkeypatch):
    """The decode step computes the first half of the slots only."""
    import jax.numpy as jnp
    from repro.models import transformer as M

    real = M.decode_step_paged

    def step(params, cfg, tokens, positions, *a, **kw):
        half = tokens.shape[0] // 2
        keep = jnp.arange(tokens.shape[0]) < half
        logits, pools = real(params, cfg, jnp.where(keep, tokens, 0),
                             positions, *a, **kw)
        return jnp.where(keep[:, None, None], logits, 0.0), pools

    monkeypatch.setattr(M, "decode_step_paged", step)


def _serve_token_altered(monkeypatch):
    """Every sampled token is moved to the next vocabulary id."""
    from repro.serving import batching

    real = batching._sample_steps
    monkeypatch.setattr(batching, "_sample_steps", lambda last, *a: (
        real(last, *a) + 1) % last.shape[-1])


@pytest.mark.parametrize("cell,plant", [
    ("tiny.train", _train_frozen),
    ("tiny.train", _train_half_batch),
    ("tiny.serve", _serve_frozen),
    ("tiny.serve", _serve_half_batch),
    ("tiny.serve", _serve_token_altered),
], ids=["train-frozen", "train-half-batch", "serve-frozen",
        "serve-half-batch", "serve-token-altered"])
def test_fault_makes_run_incorrect(root, monkeypatch, cell, plant):
    from repro.serving import batching

    plant(monkeypatch)
    batching.clear_executable_cache()
    try:
        result, checks = one_run(root, cell, seconds=2.0)
    finally:
        monkeypatch.undo()
        batching.clear_executable_cache()
    assert not result["correct"], checks


def test_control_is_not_correct(root):
    """The reference with int8 weights in the program's place fails the
    tiny cells' limits (their program reads float32 to rounding), through
    the check a run makes."""
    from bench import harness as H
    from bench.harness import Spans

    for name in ("tiny.train", "tiny.serve"):
        cell = H.find_cell(name, root)
        drv = H.driver_module(cell.traffic["driver"]).Driver(
            cell, 3000000009, Spans(False))
        drv.setup(2.0)
        drv.window(2.0)
        drv.release()
        checks = drv.check(control=True)
        assert not all(v <= lim for _, v, lim in checks), checks


def test_command_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen3-4b.train.wash", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
