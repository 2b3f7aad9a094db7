"""The DeepSeek-V2 architecture module on the CPU: a tiny copy of its
configuration goes through the harness's run, the program's first
training loss equals the reference's, and the real configuration's
weights have the program's layout."""

import json
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import run as RUN  # noqa: E402
from bench.tests import tiny  # noqa: E402

CONFIG = json.loads((ROOT / "bench/configs/deepseek-v2-lite.json").read_text())
#: the real configuration at widths a test can hold: one dense and two
#: expert layers, 4 of 8 experts held (the second share), top 3, yarn over
#: an original context of 16 positions
TINY = dict(
    CONFIG, hidden_size=64, intermediate_size=96, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, moe_intermediate_size=24,
    n_routed_experts=4, router_experts=8, first_held_expert=4,
    num_experts_per_tok=3, num_hidden_layers=3, vocab_size=256,
    torch_dtype="float32",
    rope_scaling=dict(CONFIG["rope_scaling"], factor=4,
                      original_max_position_embeddings=16))
#: float32 program against the float32 reference: they differ by rounding
#: alone, a gap far under the int8 control's (checked below)
SERVE_WORKLOAD = dict(tiny.SERVE_WORKLOAD,
                      limits={"mean_logit_gap": 1e-4, "logit_gap": 1e-3})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny cells with ``tiny_ds`` beside them: its serving cell over
    the backlog mix and a training cell."""
    root = tiny.write(tmp_path_factory.mktemp("tiny_ds"))
    (root / "bench/configs/tiny_ds.json").write_text(json.dumps(TINY))
    (root / "bench/workloads/tiny_ds.serve.json").write_text(
        json.dumps(SERVE_WORKLOAD))
    (root / "bench/workloads/tiny_ds.train.json").write_text(
        json.dumps(tiny.TRAIN_WORKLOAD))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_ds", "source": "test",
                            "file": "bench/configs/tiny_ds.json",
                            "reduced": []})
    spec["workloads"] += [
        {"name": "tiny_ds.serve", "config": "tiny_ds",
         "traffic": "backlog.tiny", "chips": 1},
        {"name": "tiny_ds.train", "config": "tiny_ds",
         "traffic": "train.tiny", "chips": 1}]
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("tiny_ds.serve")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_tiny_deepseek_serves_correctly_through_the_harness(root):
    args = RUN.parse(["--workload", "tiny_ds.serve", "--seed", "3100000013",
                      "--seconds", "2", "--trace", "0"])
    result, checks = RUN.run(args, root=root, check_device=False,
                             peak=tiny.PEAK, t_start=time.perf_counter())
    assert result["correct"], checks
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_tiny_deepseek_control_fails_the_limits(root):
    cell = H.find_cell("tiny_ds.serve", root)
    drv = H.driver_module("serve").Driver(cell, 3100000017, H.Spans(False))
    drv.setup(2.0)
    drv.window(2.0)
    drv.release()
    checks = drv.check(control=True)
    assert not all(v <= lim for _, v, lim in checks), checks


def test_first_training_loss_equals_the_reference_loss(root):
    """The fused training chunk's first loss (dense and expert stacks,
    WASH layer ids from the program) against the reference's ``loss`` of
    the same weights and batch; float32, so rounding alone separates
    them."""
    cell = H.find_cell("tiny_ds.train", root)
    drv = H.driver_module("train").Driver(cell, 3100000019, H.Spans(False))
    drv.setup(1.0)
    prog = drv.readings["losses"][0]
    ref = drv.reference()["losses"][0]
    assert abs(prog - ref) <= 1e-5 * abs(ref), (prog, ref)


def test_real_configuration_has_the_programs_layout():
    """Shapes only: the published widths, 1 dense and 4 expert layers, 16
    of 64 experts, a quarter of the vocabulary."""
    from bench.system import check_layout

    A = H.architecture("deepseek_v2")
    a = A.Arch.from_config(CONFIG)
    cfg = A.program_config(a, "deepseek-v2-lite")
    shape = jax.eval_shape(lambda k: A.init_weights(k, a), jax.random.key(0))
    check_layout(shape, cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shape))
    assert 0.86e9 < n < 0.87e9, n
    assert shape["blocks"]["mlp"]["router"].shape == (4, 2048, 64)
    assert shape["blocks"]["mlp"]["experts"]["w1"].shape == (4, 16, 2048,
                                                             1408)
    assert shape["dense_blocks"]["mlp"]["w1"].shape == (1, 2048, 10944)


def test_counts_of_the_latent_kernel():
    """The kernel's FLOPs are 2 H (r + rope + r) a cached position and
    layer; its bytes a latent page of 1,152 B a position, plus float32
    queries and outputs."""
    A = H.architecture("deepseek_v2")
    a = A.Arch.from_config(CONFIG)
    assert A.decode_kernel_flops(a, [10, 20]) == 5 * 2 * 16 * 1088 * 30
    qo = 2 * 3 * 16 * 576 * 4
    assert A.decode_kernel_bytes(a, 7, 3, 64) == 5 * (7 * 64 * 1152 + qo)
    # the held share of the routed experts: top 6 of 64, 16 held
    per_token = A.moe_matmul_params(a)
    assert per_token == 2048 * 64 + 3 * 2048 * 1408 * (2 + 1.5)


def test_yarn_frequencies_follow_the_published_rule():
    A = H.architecture("deepseek_v2")
    a = A.Arch.from_config(CONFIG)
    f = 1e4 ** (-np.arange(0, 64, 2) / 64)
    r = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(A.yarn_inv_freq(a), f / 40 * r + f * (1 - r),
                               rtol=1e-12)
    assert abs(A.softmax_scale(a) - 0.1147214) < 1e-6


def test_latent_roofline_reads_the_latent_kernel_alone():
    """The reader matches the latent kernel by its operands and leaves
    out the expert layers' grouped matmuls, which also lead with s32."""
    import types

    from bench.trace import Op

    pallas = 'custom_call_target="tpu_custom_call"'
    ops = [  # (text, seconds)
        ("%latent_attention.3 = f32[32,32,1152]{2,1,0} custom-call(s32[32,"
         "528]{1,0} %p0, s32[32]{0} %p1, f32[32,32,1152]{2,1,0} %q, bf16["
         f"84485,32,1152]{{3,2,1,0}} %pool), {pallas}", 2.0),
        ("%ragged-dot = bf16[192,1408]{1,0} custom-call(s32[1]{0} %m, s32["
         f"17]{{0}} %g, bf16[192,2048]{{1,0}} %x, bf16[16,2048,1408] %w), "
         f"{pallas}", 5.0)]
    trace = types.SimpleNamespace(
        op_count=lambda m: sum(1 for t, _ in ops if m(Op(t, 0, 1, "m"))),
        op_seconds=lambda m: sum(s for t, s in ops if m(Op(t, 0, 1, "m"))))
    A = H.architecture("deepseek_v2")
    ctx = types.SimpleNamespace(
        trace=trace, arch=A.Arch.from_config(CONFIG),
        counters={"kernel_flops": 0, "kernel_bytes": 819e9},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    read = RUN.load_reader("latent_attention_roofline.offline")
    assert read(ctx) == pytest.approx(50.0)   # 1 s of bytes in 2 s
    ctx.arch = H.architecture("dense_gqa").Arch.from_config(tiny.TINY_CONFIG)
    assert read(ctx) is None


def test_moe_share_counts_the_grouped_matmuls(monkeypatch):
    """The expert layer's share holds the ops under its scope and the
    grouped matmuls, which the chip's compiler leaves without a scope,
    and nothing else; with no ``moe`` scope in the trace it reads
    nothing."""
    import types

    from bench import program_trace as PT
    from bench.trace import Op, Trace

    s = 1_000_000_000
    scoped = {  # op text -> (scope, start, end)
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)":
            ("jit(cont_decode)/mlp/moe/route", 0, s),
        "%ragged-dot-none = bf16[192,1408]{1,0} custom-call(s32[1]{0} %m)":
            ("ragged-dot-none", s, 3 * s),
        "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %y)":
            ("jit(cont_decode)/attention", 3 * s, 7 * s)}
    trace = Trace(ops={0: [Op(text, a, b, "jit_cont_decode#1")
                           for text, (_, a, b) in scoped.items()]},
                  spans=[], window=(0, 8 * s))
    fake = types.SimpleNamespace(
        has_scope=lambda name: name == "moe",
        scope=lambda chip, op: scoped[op.text][0])
    monkeypatch.setattr(PT, "for_trace", lambda t, root: fake)
    read = RUN.load_reader("moe_share.offline")
    ctx = types.SimpleNamespace(trace=trace)
    assert read(ctx) == pytest.approx(100.0 * 3 / 7)
    fake.has_scope = lambda name: False
    assert read(ctx) is None
