"""The program's names on the profiler's clock.

Telemetry spans open a TraceMe whenever telemetry is enabled, so any
profiler session — not only a ``--profile-dir`` window — holds them;
their records carry the start on the profiler's host clock, the
enclosing span and the request they serve.  The serving programs and the
train chunk compile under the names of their compile counters and carry
``jax.named_scope``s in their HLO metadata.  The benchmark reads both
back from a trace file (``bench/xplane.py``, ``bench/program_trace.py``):
checked here on a traced CPU run of the tiny backlog cell and on the
probe trace recorded on a TPU v5e.
"""

import glob
import json
import pathlib
import re
import shutil
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import transformer as M
from repro.serving import batching
from repro.serving import engine as serving
from repro.serving.driver import RequestDriver

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import kernels, program_trace as PT, trace as TR, xplane  # noqa: E402
from bench import run as RUN  # noqa: E402
from bench.tests import tiny  # noqa: E402

PROBE = ROOT / "bench" / "tests" / "data" / "probe.xplane.pb"
SERVE_CFG = ModelConfig(num_layers=2, d_model=32, num_heads=4,
                        num_kv_heads=2, d_ff=64, vocab_size=50,
                        dtype="float32")
DECODE_CHILDREN = {"serve.decode_prepare", "serve.decode_dispatch",
                   "serve.decode_wait", "serve.decode_commit"}
NEW_METRICS = (("tick_host_ms.offline", "ms", "tiny.backlog"),
               ("host_idle_share.offline", "%", "tiny.backlog"),
               ("mixing_share.train", "%", "tiny.train"))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.reset()
    yield
    obs.reset()


def _params():
    return serving.averaged_params(
        jax.vmap(lambda k: M.init_params(k, SERVE_CFG))(
            jax.random.split(jax.random.key(0), 2)))


def _serve(n=3, **server_kw):
    """A short stream through the request driver."""
    server = batching.ContinuousServer(
        _params(), SERVE_CFG, page_size=4, max_slots=2, num_pages=32,
        **server_kw)
    driver = RequestDriver(server, prefill_chunk=4)
    rng = np.random.default_rng(0)
    for i in range(n):
        driver.submit(batching.Request(
            f"r{i}", rng.integers(0, SERVE_CFG.vocab_size, 6 + i,
                                  dtype=np.int32), 3))
    driver.drain()
    return server


# -- span records -------------------------------------------------------


def test_span_record_carries_start_parent_and_uid():
    tel = obs.configure(memory=True)
    before = time.time_ns()
    with tel.span("x.outer"):
        with tel.span("x.inner", uid="r7") as attrs:
            attrs["tokens"] = 5
    mem = tel._sinks[-1]
    (inner,) = mem.named("x.inner")
    (outer,) = mem.named("x.outer")
    assert outer["parent"] is None and inner["parent"] == "x.outer"
    assert inner["uid"] == "r7" and inner["tokens"] == 5
    assert before <= outer["t0_ns"] <= inner["t0_ns"] <= time.time_ns()
    assert inner["dur_s"] <= outer["dur_s"]


def test_span_parent_is_per_thread():
    import threading

    tel = obs.configure(memory=True)
    seen = threading.Event()
    release = threading.Event()

    def other():
        with tel.span("x.other"):
            seen.set()
            release.wait(5)

    t = threading.Thread(target=other)
    with tel.span("x.main"):
        t.start()
        seen.wait(5)
        with tel.span("x.child"):
            pass
        release.set()
        t.join()
    mem = tel._sinks[-1]
    assert mem.named("x.other")[0]["parent"] is None
    assert mem.named("x.child")[0]["parent"] == "x.main"


def test_decode_step_holds_its_four_phases():
    tel = obs.configure(memory=True)
    _serve()
    recs = [r for r in tel._sinks[-1].records if r.get("kind") == "span"]
    steps = [r for r in recs if r["name"] == "serve.decode_step"]
    assert steps and all(r["parent"] == "serve.tick" for r in steps)
    for name in DECODE_CHILDREN:
        kids = [r for r in recs if r["name"] == name]
        assert len(kids) == len(steps)
        assert {r["parent"] for r in kids} == {"serve.decode_step"}
    chunks = [r for r in recs if r["name"] == "serve.prefill_chunk"]
    assert chunks and all(r["uid"].startswith("r") and r["tokens"] > 0
                          for r in chunks)
    # an admission that finds no slot free returns before it counts the
    # cached prefix: each request is admitted once with the count
    admits = [r for r in recs if r["name"] == "serve.admit"
              and "cached_tokens" in r]
    assert sorted(r["uid"] for r in admits) == ["r0", "r1", "r2"]
    waits = [r for r in recs if r["name"] == "serve.prefill_wait"]
    assert len(waits) == 3
    assert {r["parent"] for r in waits} == {"serve.prefill_chunk"}


def test_span_stream_passes_the_schema_check(tmp_path):
    path = tmp_path / "serve.jsonl"
    obs.configure(jsonl=str(path))
    _serve(n=2)
    obs.get().finalize()
    from tools.check_metrics_schema import check_stream
    assert check_stream(str(path)) == []
    spans = [json.loads(line) for line in open(path)
             if '"kind": "span"' in line]
    assert spans and all("t0_ns" in r and "parent" in r for r in spans)


def test_program_spans_reach_a_trace_someone_else_started(tmp_path):
    from jax.profiler import ProfileData

    tel = obs.get()
    assert tel._profile is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tel.span("serve.tick"):
            with tel.span("serve.admit", uid="r1"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    events[ev.name] = (ev.start_ns, ev.end_ns)
    assert set(events) == {"serve.tick", "serve.admit"}
    (ts, te), (as_, ae) = events["serve.tick"], events["serve.admit"]
    assert ts <= as_ < ae <= te


# -- names and scopes in the compiled programs -------------------------


def _decode_args(server):
    *host, _ = server._decode_inputs()
    arrays = [*jax.device_put(host), server._keys, server._temp]
    if server.speculative:
        return (server.params, server._draft_params, server._pools["k"],
                server._pools["v"], server._dpools["k"], server._dpools["v"],
                *arrays)
    return (server.params, server._pools["k"], server._pools["v"], *arrays)


def _lowered_train_chunk():
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.core.mixing import MixingConfig
    from repro.optim import make_optimizer
    from repro.train import engine as T

    cfg = SERVE_CFG
    n, steps, batch, seq = 2, 2, 2, 8
    member = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.key(0))
    mesh = jax.make_mesh((1,), ("ens",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    opt_init, opt_update = make_optimizer("sgd", momentum=0.9)
    mcfg = MixingConfig(kind="wash", base_p=0.3, mode="bucketed")
    pop = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), member)
    opt = jax.eval_shape(jax.vmap(opt_init), pop)
    spec = lambda tree: jax.tree_util.tree_map(lambda _: P("ens"), tree)  # noqa: E731
    fn = T.make_fused_chunk_fn(
        mesh, mcfg, infer_layer_ids(member, cfg.num_layers),
        total_layers(cfg.num_layers), opt_update,
        lambda p, b: M.loss_fn(p, cfg, b)[0], spec(pop), spec(opt),
        {"tokens": P(None, "ens")}, use_pallas=True)
    sds = jax.ShapeDtypeStruct
    keydata = jax.eval_shape(
        lambda: jax.vmap(lambda t: jax.random.key_data(
            jax.random.fold_in(jax.random.key(0), t)))(jnp.arange(steps)))
    return fn.lower(
        pop, opt, {"tokens": sds((steps, n, batch, seq), jnp.int32)},
        sds((steps,), jnp.float32), keydata, sds((steps,), jnp.float32),
        sds((), jnp.int32))


def _lowered(program):
    if program == "train_chunk":
        return _lowered_train_chunk()
    params = _params()
    if program in ("cont_decode", "cont_spec_decode"):
        server = batching.ContinuousServer(
            params, SERVE_CFG, page_size=4, max_slots=2, num_pages=16,
            use_pallas=True, speculative=program == "cont_spec_decode",
            draft_k=2)
        return server._decode.lower(*_decode_args(server))
    server = batching.ContinuousServer(params, SERVE_CFG, page_size=4,
                                       max_slots=2, num_pages=16)
    key = jax.random.split(jax.random.key(0), 1)[0]
    temp = jnp.float32(1e-6)
    if program == "cont_prefill_chunk":
        fn = batching._chunk_program(SERVE_CFG, False, 4, server.max_pages,
                                     4, 16, True)
        return fn.lower(params, None, server._pools["k"], server._pools["v"],
                        None, None, jnp.zeros((4,), jnp.int32), jnp.int32(0),
                        jnp.zeros((server.max_pages,), jnp.int32), key, temp)
    fn = batching._admit_program(SERVE_CFG, False, 6, 2, 4, 16, True)
    return fn.lower(params, server._pools["k"], server._pools["v"],
                    jnp.zeros((1, 6), jnp.int32), jnp.ones((2,), jnp.int32),
                    jnp.ones((2,), bool), key, temp)


#: each program, and scopes its compiled ops must carry
PROGRAMS = {
    "train_chunk": ("loss", "optimizer", "wash/plan", "wash/shuffle",
                    "attention", "mlp", "logits"),
    "cont_decode": ("attention/kv_write", "attention/paged_attention",
                    "mlp", "logits", "sample"),
    "cont_spec_decode": ("attention/kv_write", "attention/paged_attention",
                         "sample"),
    "cont_prefill_chunk": ("attention/kv_write", "mlp", "sample"),
    "cont_prefill_admit": ("mlp", "logits", "sample"),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_programs_compile_under_their_names_with_scopes(program):
    lowered = _lowered(program)
    assert f"module @jit_{program} " in lowered.as_text()
    hlo = lowered.compile().as_text()
    # a fused op may join its ops' names with ";"
    paths = {"/" + "/".join(PT.segments(name)) + "/"
             for names in re.findall(r'op_name="([^"]+)"', hlo)
             for name in names.split(";")}
    assert any(p.startswith(f"/{program}/") for p in paths)
    for scope in PROGRAMS[program]:
        assert any(f"/{scope}/" in p for p in paths), (program, scope)


# -- the benchmark's readers of the program's names ----------------------


def test_scope_segments_drop_transforms():
    assert PT.segments("jit(train_chunk)/jit(main)/while/body/"
                       "vmap(transpose(jvp(loss)))/mlp/dot_general") == [
        "train_chunk", "main", "while", "body", "loss", "mlp",
        "dot_general"]
    assert "wash" in PT.segments("jit(train_chunk)/wash/shuffle/pallas_call")
    assert "wash" not in PT.segments("jit(wash_shuffle)/pallas_call")


def test_innermost_and_overlap():
    spans = [("serve.tick", 0, 100), ("serve.decode_step", 10, 90),
             ("serve.decode_wait", 40, 80), ("serve.emit", 90, 95)]
    assert PT.innermost(spans) == [
        (0, 10, "serve.tick"), (10, 40, "serve.decode_step"),
        (40, 80, "serve.decode_wait"), (80, 90, "serve.decode_step"),
        (90, 95, "serve.emit"), (95, 100, "serve.tick")]
    pieces = [(0, 10), (20, 30), (40, 50)]
    assert PT.overlap(pieces, 5, 45) == 5 + 10 + 5
    assert PT.overlap(pieces, 10, 20) == 0
    assert PT.overlap(pieces, 0, 100) == 30


@pytest.fixture(scope="module")
def probe_root(tmp_path_factory):
    """A checkout holding the repo's readers and the probe as a run's
    trace."""
    root = tmp_path_factory.mktemp("probe")
    dest = root / PT.TRACE_DIR / "probe.1"
    dest.mkdir(parents=True)
    shutil.copy(PROBE, dest / PROBE.name)
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    return root


def test_xplane_names_both_kernels_scopes(probe_root):
    tr = TR.load(str(PROBE))
    pt = PT.for_trace(tr, probe_root)
    assert pt is not None and pt.spans == []
    assert xplane.op_scopes(str(PROBE)).keys() == {0}
    for match in (kernels.paged_attention, kernels.wash_shuffle):
        ops = [op for op in tr.ops[0] if match(op)]
        assert len(ops) == 3
        assert all(pt.scope(0, op).endswith("pallas_call") for op in ops)
    assert not pt.has_scope("wash")


def test_probe_readings_unchanged(probe_root):
    """The seven accepted per-layer readers and the breakdown read the
    probe as they did before the program named its work."""
    tr = TR.load(str(PROBE))
    ctx = types.SimpleNamespace(
        trace=tr, chips=1, arch=None,
        counters={"kernel_flops": 1e9, "kernel_bytes": 1e7,
                  "shuffle_bytes": 4e6},
        peaks={"bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11})
    want = {"idle_share.train": 96.23614655191056,
            "idle_share.offline": 96.23614655191056,
            "sort_share.train": 3.0154443897832186,
            "wash_shuffle_roofline": 45.31037094354656,
            "paged_attention_roofline.offline": 30.1154602654208}
    for name, value in want.items():
        assert RUN.load_reader(name)(ctx) == value
    assert tr.top_ops(2) == [
        ("jit__lambda#2242/fusion", 0.000181682),
        ("jit_bucketed_shuffle_pallas#1650/copy_bitcast_fusion",
         0.00013442)]
    assert tr.idle_gaps(2) == [("bench.host_wait", 0.003724672),
                               ("bench.host_wait", 0.002893945)]
    # the new readers find no program span and no ``wash`` scope there
    for name, _, _ in NEW_METRICS:
        assert RUN.load_reader(name, probe_root)(ctx) is None


@pytest.fixture(scope="module")
def backlog(tmp_path_factory):
    """A traced CPU run of the tiny backlog cell with the new metrics."""
    root = tiny.write(tmp_path_factory.mktemp("tiny"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"] += [{"name": n, "unit": u, "workloads": [c]}
                          for n, u, c in NEW_METRICS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    seed = 3000000011
    args = RUN.parse(["--workload", "tiny.backlog", "--seed", str(seed),
                      "--seconds", "1.0", "--trace", "1"])
    result, checks = RUN.run(args, root=root, check_device=False,
                             peak=tiny.PEAK, t_start=time.perf_counter())
    tr = TR.load(str(root / PT.TRACE_DIR / f"tiny.backlog.{seed}"))
    return result, checks, PT.for_trace(tr, root)


def test_backlog_trace_holds_the_program_spans(backlog):
    result, checks, pt = backlog
    assert result["correct"], checks
    names = {n for n, _, _ in pt.spans}
    assert {"serve.tick", "serve.admit", "serve.prefill_chunk",
            "serve.emit", "serve.decode_step"} | DECODE_CHILDREN <= names
    ticks = pt.named("serve.tick")
    end = max(b for _, _, b in pt.spans)   # spans are clipped to the window
    for s, e in pt.named("serve.decode_step"):
        assert any(ts <= s and e <= te for ts, te in ticks)
        kids = {n for n, a, b in pt.spans
                if n in DECODE_CHILDREN and s <= a and b <= e}
        assert kids == DECODE_CHILDREN or e == end


def test_backlog_reads_tick_host_time_but_no_idle_share(backlog):
    result, _, _ = backlog
    metrics = result["metrics"]
    assert metrics["tick_host_ms.offline"]["value"] > 0
    # the CPU run has no device plane, so no idle share of any kind
    assert "host_idle_share.offline" not in metrics
    assert result["device"]["busy_s"] == 0.0
