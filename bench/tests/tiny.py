"""A tiny copy of the benchmark's cells for runs on the CPU: the same
drivers, references and checks, at widths a test can hold."""

from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_CONFIG = {
    "source": "tiny dense decoder for tests",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "qk_norm": True, "qkv_bias": True, "tie_word_embeddings": False,
    "torch_dtype": "float32",
}

TRAIN_TRAFFIC = {"driver": "train", "batch": 2, "seq_len": 16}
TRAIN_WORKLOAD = {
    "population": 2, "record_every": 5, "optimizer": "sgd", "lr": 0.1,
    "momentum": 0.9, "weight_decay": 1e-4, "mixing": "wash",
    "mode": "bucketed", "base_p": 0.3, "pallas_shuffle": True,
    "limits": {"loss_gap": 1e-4, "grad_gap": 1e-3, "delta_gap": 1e-3,
               "grad_err": 1e-3},
}
SERVE_TRAFFIC = {
    "driver": "serve", "arrivals": "poisson", "rate": 40.0,
    "distinct_sizes": 4,
    "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
    "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 6},
}
BACKLOG_TRAFFIC = dict(SERVE_TRAFFIC, arrivals="backlog", requests=24,
                       warm_start=True)
SERVE_WORKLOAD = {"population": 2, "page_size": 4, "max_slots": 4,
                  "prefill_chunk": 8, "limits": {"mean_logit_gap": 1e-4,
                                                 "logit_gap": 1e-3}}

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def write(root: pathlib.Path, config: dict = None) -> pathlib.Path:
    """A checkout-like tree at ``root`` with the cells ``tiny.train`` and
    ``tiny.serve``; returns ``root``."""
    def put(rel, obj):
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))

    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics",
                    dirs_exist_ok=True)
    put("bench/configs/tiny.json", config or TINY_CONFIG)
    put("bench/traffic/train.tiny.json", TRAIN_TRAFFIC)
    put("bench/traffic/serve.tiny.json", SERVE_TRAFFIC)
    put("bench/workloads/tiny.train.json", TRAIN_WORKLOAD)
    put("bench/workloads/tiny.serve.json", SERVE_WORKLOAD)
    put("bench/traffic/backlog.tiny.json", BACKLOG_TRAFFIC)
    put("bench/workloads/tiny.backlog.json", SERVE_WORKLOAD)
    cells = ["tiny.train", "tiny.serve", "tiny.backlog"]
    put("BENCHMARK.json", {
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": []}],
        "workloads": [
            {"name": "tiny.train", "config": "tiny", "traffic": "train.tiny",
             "chips": 1},
            {"name": "tiny.serve", "config": "tiny", "traffic": "serve.tiny",
             "chips": 1},
            {"name": "tiny.backlog", "config": "tiny",
             "traffic": "backlog.tiny", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "workloads": ["tiny.train"]},
            {"name": "serve_tokens_per_s", "unit": "tokens/s",
             "workloads": ["tiny.serve", "tiny.backlog"]},
            {"name": "ttft_p95_ms", "unit": "ms", "workloads": ["tiny.serve"]},
            {"name": "itl_p95_ms", "unit": "ms", "workloads": ["tiny.serve"]}],
        "per_layer": [
            {"name": n, "unit": "%", "workloads": [c]} for n, c in (
                ("mfu.train", cells[0]), ("idle_share.train", cells[0]),
                ("idle_share.online", cells[1]))],
    })
    return root
