"""``chip_smoke.py``: its phases at a tiny size on the CPU, and its refusal
to run anywhere but on a TPU.

The script's one- and four-chip phases run here on a tiny bf16 config with
the Pallas kernels in interpret mode — the same control flow, checks and
tolerances as on the chip, at a size the CPU runs in seconds.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as C
from repro.configs.base import ModelConfig
cfg = ModelConfig(name="tiny", num_layers=2, d_model=64, num_heads=4,
                  num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
                  qk_norm=True, rope_theta=1e6, dtype="bfloat16")
"""


def _run(src: str, devices: int = 1, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)], capture_output=True,
        text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_chip_smoke_refuses_a_host_without_tpu():
    r = _run("import runpy; runpy.run_path('chip_smoke.py', "
             "run_name='__main__')")
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


@pytest.mark.parametrize("path,devices,call", [
    ("one_chip", 1,
     "C.one_chip(cfg, C.Sizes(batch=2, seq_len=32, requests=4, "
     "max_prompt=32, max_new=8, page_size=8, max_slots=2, "
     "prefill_chunk=16), use_pallas=True)"),
    ("four_chips", 4,
     "C.four_chips(cfg, C.Sizes(population=4, batch=2, seq_len=32, "
     "steps=3))"),
])
def test_chip_smoke_phases_pass_at_tiny_size(path, devices, call):
    r = _run(TINY.format(repo=REPO) + call + "\nprint('PHASES OK')\n",
             devices=devices)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "PHASES OK" in r.stdout
    assert "ok: " in r.stdout
