"""Instrumentation inertness: telemetry-on vs telemetry-off runs are
bitwise identical in outputs and identical in compile counts.

Every obs hook in the engines is a host-side Python effect (a registry
write, a sink append) — nothing is traced, no device sync is added.
This suite is the contract: for the fused train engine (ens mesh and the
pipelined S=1 delegation path), the scan serving engine, and the
continuous-batching driver, a run with EVERY sink enabled must produce
the same bits and the same executable counts as a run with telemetry
hard-disabled.  It also pins the comm-volume events to the exact
``static_mix_comm`` accounting, bit-for-bit.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import MixingConfig, static_mix_comm
from repro.core import population as pop
from repro.models import transformer as M
from repro.serving import batching
from repro.serving import engine as serving
from repro.serving.driver import RequestDriver

from tests.conftest import tiny_data_fn, tiny_init, tiny_loss_fn

TCFG = TrainConfig(population=2, optimizer="sgd", lr=0.05, total_steps=6,
                   batch_size=4, seq_len=16, seed=0)
MCFG = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
SERVE_CFG = ModelConfig(num_layers=2, d_model=32, num_heads=4,
                        num_kv_heads=2, d_ff=64, vocab_size=50,
                        dtype="float32")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.reset()
    yield
    obs.reset()


def _all_sinks(tmp_path):
    """Every sink the subsystem has, all attached at once."""
    return obs.configure(jsonl=str(tmp_path / "events.jsonl"),
                        memory=True, console=True)


def _assert_trees_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# fused train engine (ens mesh + the pipelined delegation path)
# ---------------------------------------------------------------------------


def _train_sharded(record_every=3):
    from repro.train.engine import train_population_sharded

    return train_population_sharded(
        jax.random.key(0), tiny_init, tiny_loss_fn, tiny_data_fn,
        TCFG, MCFG, num_blocks=2, record_every=record_every,
    )


def test_train_engine_inert(tmp_path):
    from repro.train import engine

    tel = obs.get()
    tel.enabled = False
    engine.reset_chunk_trace_count()
    off = _train_sharded()
    traces_off = engine.chunk_trace_count()

    _all_sinks(tmp_path)
    engine.reset_chunk_trace_count()
    on = _train_sharded()
    traces_on = engine.chunk_trace_count()

    assert traces_on == traces_off <= 2
    _assert_trees_bitwise(off.population, on.population)
    _assert_trees_bitwise(off.opt_state, on.opt_state)
    assert off.comm_scalars == on.comm_scalars  # bitwise float equality
    for k in ("step", "loss", "consensus", "comm"):
        assert off.history[k] == on.history[k]


@pytest.mark.slow
def test_pipelined_engine_inert(tmp_path):
    """Same contract on the pipelined engine with real stages (S=2), which
    needs a forced multi-device CPU host, hence the subprocess (jax locks
    the device count at first init — see tests/test_pipeline.py)."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    src = textwrap.dedent(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax import lax
        from repro import obs
        from repro.configs.base import TrainConfig
        from jax.sharding import AxisType
        from repro.core.mixing import MixingConfig
        from repro.train import StageFns, engine
        from repro.train.engine import train_population_pipelined

        def init(k):
            ks = jax.random.split(k, 3)
            return {{"embed": {{"w": jax.random.normal(ks[0], (16, 8)) * .3}},
                    "blocks": {{"w1": jax.random.normal(ks[1], (4, 8, 8)) * .3}},
                    "head": {{"w": jax.random.normal(ks[2], (8, 4)) * .3}}}}

        def data_fn(m, step, k):
            return {{"x": jax.random.normal(k, (4, 16)),
                    "y": jax.random.normal(jax.random.fold_in(k, 1), (4, 4))}}

        def blocks(p, x):
            h, _ = lax.scan(lambda h, wl: (jnp.tanh(h @ wl) + h, None),
                            x, p["blocks"]["w1"])
            return h

        fns = StageFns(lambda p, b: b["x"] @ p["embed"]["w"], blocks,
                       lambda p, x, b: jnp.mean((x @ p["head"]["w"]
                                                 - b["y"]) ** 2))
        tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.05,
                           total_steps=6, batch_size=4, seq_len=16, seed=0)
        mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        mesh = jax.make_mesh((2, 1, 2), ("ens", "data", "pipe"),
                             axis_types=(AxisType.Auto,) * 3)

        def run():
            engine.reset_chunk_trace_count()
            res = train_population_pipelined(
                jax.random.key(0), init, fns, data_fn, tcfg, mcfg,
                num_blocks=4, record_every=3, mesh=mesh, microbatches=2)
            return res, engine.chunk_trace_count()

        obs.get().enabled = False
        off, t_off = run()
        obs.configure(jsonl={str(tmp_path / 'pipe.jsonl')!r}, memory=True)
        on, t_on = run()
        assert t_on == t_off <= 2, (t_on, t_off)
        for a, b in zip(jax.tree_util.tree_leaves(off.population),
                        jax.tree_util.tree_leaves(on.population)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert off.comm_scalars == on.comm_scalars
        assert off.history["loss"] == on.history["loss"]
        print("pipelined-inert-ok")
    """)
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, timeout=420, env=env, cwd=repo)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "pipelined-inert-ok" in r.stdout
    # the stream the instrumented subprocess run produced validates
    from tools.check_metrics_schema import check_stream
    assert check_stream(str(tmp_path / "pipe.jsonl")) == []


def test_train_comm_events_match_static_accounting(tmp_path):
    """The emitted comm-volume events ARE the exact static accounting:
    per-mix-step scalars equal static_mix_comm, and the cumulative totals
    replay bit-for-bit (the schema checker re-verifies this in CI)."""
    tel = _all_sinks(tmp_path)
    mem = None
    for s in tel._sinks:
        if isinstance(s, obs.MemorySink):
            mem = s
    res = _train_sharded()

    population = pop.init_population(tiny_init, jax.random.key(0),
                                     TCFG.population,
                                     same_init=TCFG.same_init)
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population)
    lids = infer_layer_ids(pop.member(population, 0), 2)
    expected_per = static_mix_comm(member_tpl, MCFG, lids, total_layers(2),
                                   TCFG.population)
    events = mem.named("train.comm_volume")
    assert events, "instrumented train run must emit comm-volume events"
    replay = 0.0
    for ev in events:
        assert ev["comm_per_mix_step"] == expected_per  # exact, not approx
        for _ in range(ev["mix_steps"]):
            replay += ev["comm_per_mix_step"]
        assert replay == ev["comm_total"]
    assert replay == res.comm_scalars
    # the registry counter mirrored the same adds
    assert tel.registry.counter("train.comm_scalars").value == res.comm_scalars

    # and the JSONL stream passes the schema checker with --require-comm
    tel.finalize()
    from tools.check_metrics_schema import check_stream
    assert check_stream(str(tmp_path / "events.jsonl"),
                        require_comm=True) == []


def test_vmap_loop_inert(tmp_path):
    from repro.train.loop import train_population

    def run():
        return train_population(
            jax.random.key(0), tiny_init, tiny_loss_fn, tiny_data_fn,
            TCFG, MCFG, num_blocks=2, record_every=3,
            record_fn=lambda step, p: {"probe": float(step)},
        )

    obs.get().enabled = False
    off = run()
    _all_sinks(tmp_path)
    on = run()
    _assert_trees_bitwise(off.population, on.population)
    assert off.history["loss"] == on.history["loss"]
    assert off.history["probe"] == on.history["probe"]
    assert off.comm_scalars == on.comm_scalars
    # record_fn results became metric samples
    assert (obs.get().registry.gauge("train.record.probe").value
            == on.history["probe"][-1])


# ---------------------------------------------------------------------------
# scan serving engine
# ---------------------------------------------------------------------------


def test_scan_engine_inert(tmp_path):
    params = M.init_params(jax.random.key(0), SERVE_CFG)
    req = {"tokens": jax.random.randint(jax.random.key(1), (2, 8), 0,
                                        SERVE_CFG.vocab_size)}

    def run():
        serving.reset_trace_counts()
        serving.clear_executable_cache()
        out = np.asarray(serving.generate(params, SERVE_CFG, req, 8))
        return out, serving.decode_trace_count(), serving.prefill_trace_count()

    obs.get().enabled = False
    out_off, dec_off, pre_off = run()
    _all_sinks(tmp_path)
    out_on, dec_on, pre_on = run()

    np.testing.assert_array_equal(out_off, out_on)
    assert (dec_on, pre_on) == (dec_off, pre_off) == (1, 1)
    # compile counters mirror the trace counters exactly
    assert obs.get().registry.counter("compile.serve_decode").value == 1
    assert obs.get().registry.counter("compile.serve_prefill").value == 1


# ---------------------------------------------------------------------------
# continuous-batching driver
# ---------------------------------------------------------------------------


def _driver_workload():
    rng = np.random.default_rng(3)
    reqs = []
    common = rng.integers(0, SERVE_CFG.vocab_size, (8,)).astype(np.int32)
    for i in range(5):
        S = int(rng.integers(2, 14))
        body = rng.integers(0, SERVE_CFG.vocab_size, (S,)).astype(np.int32)
        if i % 2:
            body = np.concatenate([common, body])
        reqs.append(batching.Request(f"r{i}", body, 4 + i % 3))
    return reqs


def test_continuous_driver_inert(tmp_path):
    params = serving.averaged_params(
        jax.vmap(lambda k: M.init_params(k, SERVE_CFG))(
            jax.random.split(jax.random.key(0), 2)))

    def run():
        batching.clear_executable_cache()
        batching.reset_trace_counts()
        server = batching.ContinuousServer(
            params, SERVE_CFG, page_size=4, max_slots=3, num_pages=64,
            retain_pages=True)
        driver = RequestDriver(server, prefill_chunk=4)
        metrics = driver.run(_driver_workload())
        toks = {uid: np.asarray(m.tokens) for uid, m in metrics.items()}
        return (toks, batching.decode_trace_count(),
                batching.prefill_trace_count())

    obs.get().enabled = False
    toks_off, dec_off, pre_off = run()
    _all_sinks(tmp_path)
    toks_on, dec_on, pre_on = run()

    assert toks_on.keys() == toks_off.keys()
    for uid in toks_off:
        np.testing.assert_array_equal(toks_off[uid], toks_on[uid])
    assert dec_on == dec_off == 1
    assert pre_on == pre_off
    reg = obs.get().registry
    assert reg.counter("compile.cont_decode").value == dec_on
    assert reg.histogram("serve.ttft_s").count == len(toks_on)
    # the JSONL stream the run produced validates
    obs.get().finalize()
    from tools.check_metrics_schema import check_stream
    assert check_stream(str(tmp_path / "events.jsonl")) == []
