"""Pipeline-stage axis: planner budgets, microbatched training engine,
stage-split decode, and the pp-aware mesh constructors.

Host-side logic (mesh shapes, schedule arithmetic, stage assignment,
support gates) runs in-process on the 1-device pytest host; everything
that needs real stages runs in a subprocess with a forced multi-device
CPU host (jax locks the device count at first init), following
tests/test_distributed.py.

Contracts asserted here:
  * ``(E, 1, 1)`` pipeline mesh with one microbatch delegates to the
    fused engine bitwise (tokens, losses, params, comm);
  * ``S=4 / M=4`` GPipe schedule matches the single-stage engine to
    tolerance, with WASH comm ≤ the single-stage plan's;
  * staged decode is bitwise-identical to the unstaged serving engine
    (greedy + temperature), compiles once per shape, and its HLO moves
    activations only one stage forward per hop;
  * the shard-local WASH mixer on an (ens, pipe) mesh lowers to
    collective-permutes that stay inside stage rings (src ≡ tgt mod S);
  * mesh constructors survive prime device counts, degenerate to
    all-ones on 1 device, and reject bad --mesh-shape overrides loudly.
"""

import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.layer_index import (
    infer_layer_ids, stage_layer_bounds, stage_of_depth, total_layers,
)
from repro.train.schedule import num_pipeline_ticks, split_microbatch_sizes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(src: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def fake_mesh(**shape):
    return types.SimpleNamespace(axis_names=tuple(shape), shape=shape)


TINY = dict(name="tiny", d_model=32, d_ff=64, num_layers=4, num_heads=4,
            num_kv_heads=2, vocab_size=64, max_position=128)


# ---------------------------------------------------------------------------
# host-side: stage assignment + schedule arithmetic
# ---------------------------------------------------------------------------


def test_stage_layer_bounds_cover_uneven_depths():
    assert stage_layer_bounds(4, 2) == ((0, 2), (2, 4))
    # kimi-style uneven split: contiguous, covering, monotone
    bounds = stage_layer_bounds(61, 4)
    assert bounds[0][0] == 0 and bounds[-1][1] == 61
    assert all(a[1] == b[0] for a, b in zip(bounds, bounds[1:]))
    assert sum(hi - lo for lo, hi in bounds) == 61
    with pytest.raises(ValueError):
        stage_layer_bounds(4, 0)


def test_stage_of_depth_owner_assignment():
    # embed (depth 0) -> first stage, head (depth L+1) -> last stage
    assert stage_of_depth(0, 4, 2) == 0
    assert stage_of_depth(5, 4, 2) == 1
    # block b sits in the stage whose bounds contain it
    for s, (lo, hi) in enumerate(stage_layer_bounds(61, 4)):
        for b in (lo, hi - 1):
            assert stage_of_depth(b + 1, 61, 4) == s


def test_pipeline_schedule_arithmetic():
    assert num_pipeline_ticks(4, 4) == 7
    assert num_pipeline_ticks(1, 1) == 1
    with pytest.raises(ValueError):
        num_pipeline_ticks(0, 2)
    assert split_microbatch_sizes(8, 4) == (4, 2)
    with pytest.raises(ValueError, match="microbatches"):
        split_microbatch_sizes(8, 3)


# ---------------------------------------------------------------------------
# host-side: stage-sharded specs + support gates
# ---------------------------------------------------------------------------


def test_stage_member_specs_targets_scanned_leaves_only():
    from repro.sharding import rules

    member = {
        "embed": {"w": jax.ShapeDtypeStruct((32, 16), jnp.float32)},
        "blocks": {"w1": jax.ShapeDtypeStruct((4, 16, 16), jnp.float32)},
        "head": {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)},
    }
    lids = infer_layer_ids(member, 4)
    specs = jax.tree_util.tree_map(
        lambda _: P(), member, is_leaf=lambda x: hasattr(x, "shape")
    )
    staged = rules.stage_member_specs(specs, lids, "pipe")
    assert staged["blocks"]["w1"] == P("pipe")
    assert staged["embed"]["w"] == P() and staged["head"]["w"] == P()
    # a layer axis already sharded by another mesh axis is an error
    specs["blocks"]["w1"] = P("model", None, None)
    with pytest.raises(ValueError, match="stage-split"):
        rules.stage_member_specs(specs, lids, "pipe")
    # population_pspecs routes through it and stacks the pop axis in front
    specs["blocks"]["w1"] = P()
    pop = rules.population_pspecs(specs, ("ens",), layer_ids=lids,
                                  pipe_axis="pipe")
    assert pop["blocks"]["w1"] == P("ens", "pipe")
    with pytest.raises(ValueError, match="layer_ids"):
        rules.population_pspecs(specs, ("ens",), pipe_axis="pipe")


def test_support_gates_reject_out_of_family_configs():
    from repro.models import transformer as M

    assert M.staged_decode_supported(ModelConfig(**TINY)) is None
    assert M.pipeline_supported(ModelConfig(**TINY)) is None
    ssm = ModelConfig(**{**TINY, "block_kind": "rwkv6"})
    assert "block_kind" in M.staged_decode_supported(ssm)
    assert "block_kind" in M.pipeline_supported(ssm)
    vlm = ModelConfig(**{**TINY, "frontend": "vision"})
    assert "frontend" in M.staged_decode_supported(vlm)
    moe_cfg = ModelConfig(**{**TINY, "moe": True, "n_routed_experts": 4,
                             "top_k": 2})
    assert M.staged_decode_supported(moe_cfg) is None  # decode is fine
    assert "aux" in M.pipeline_supported(moe_cfg)  # training is not
    with pytest.raises(NotImplementedError, match="block_kind"):
        M.pipeline_stage_fns(ssm)


def test_generate_rejects_bad_staged_requests():
    from repro.models import transformer as M
    from repro.serving import engine as serving

    cfg = ModelConfig(**TINY)
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32)}
    mesh = fake_mesh(pipe=4)
    with pytest.raises(ValueError, match="ensemble"):
        serving.generate(params, cfg, batch, 4, mode="ensemble", mesh=mesh)
    cfg5 = ModelConfig(**{**TINY, "num_layers": 5})
    with pytest.raises(ValueError, match="split evenly"):
        serving.generate(params, cfg5, batch, 4, mesh=mesh)
    ssm = ModelConfig(**{**TINY, "block_kind": "rwkv6"})
    with pytest.raises(NotImplementedError, match="staged decode"):
        serving.generate(params, ssm, batch, 4, mesh=mesh)
    with pytest.raises(ValueError, match="pipe-only"):
        serving.generate(params, cfg, batch, 4,
                         mesh=fake_mesh(data=2, pipe=4))


# ---------------------------------------------------------------------------
# host-side: mesh constructors (1-device degeneration + shape overrides)
# ---------------------------------------------------------------------------


def test_make_host_mesh_pipe_kinds_degenerate_on_one_device():
    from repro.launch.mesh import make_host_mesh

    assert dict(make_host_mesh(4, "ens_pp").shape) == {"ens": 1, "pipe": 1}
    assert dict(make_host_mesh(4, "ens_dp_pp").shape) == {
        "ens": 1, "data": 1, "pipe": 1}
    with pytest.raises(ValueError, match="pp_stages"):
        make_host_mesh(4, "ens_pp", pp_stages=2)


def test_make_host_mesh_shape_override_validation():
    from repro.launch.mesh import make_host_mesh

    # wrong arity for the kind
    with pytest.raises(ValueError, match="does not match"):
        make_host_mesh(4, "ens_dp", mesh_shape=(1, 1, 1))
    # needs more devices than the host has
    with pytest.raises(ValueError, match="divide this host's"):
        make_host_mesh(4, "ens_dp", mesh_shape=(2, 2))
    # a valid all-ones override works on any host
    assert dict(make_host_mesh(4, "ens_dp_pp",
                               mesh_shape=(1, 1, 1)).shape) == {
        "ens": 1, "data": 1, "pipe": 1}


@pytest.mark.slow
def test_make_host_mesh_prime_device_count():
    """A 7-device host: nothing divides, so auto-fill falls back to 1s
    where it must, and explicit shapes that fit are honored."""
    _run("""
        import jax
        from repro.launch.mesh import make_host_mesh
        assert len(jax.devices()) == 7
        # population 4: largest divisor of 4 that fits 7 devices is 4,
        # leaving 7//4 -> rest 1
        assert dict(make_host_mesh(4, "ens_dp").shape) == {"ens": 4, "data": 1}
        assert dict(make_host_mesh(7, "ens_pp").shape) == {"ens": 7, "pipe": 1}
        assert dict(make_host_mesh(14, "ens").shape) == {"ens": 7}
        # 7 is prime: an ens=7 pipe=1 explicit shape is the only full fill
        m = make_host_mesh(7, "ens_pp", mesh_shape=(7, 1))
        assert dict(m.shape) == {"ens": 7, "pipe": 1}
        try:
            make_host_mesh(4, "ens_pp", mesh_shape=(2, 2))
            raise SystemExit("4 devices do not divide 7")
        except ValueError as e:
            assert "divide this host's" in str(e)
        print("OK prime mesh")
    """, devices=7)


@pytest.mark.slow
def test_make_host_mesh_auto_fill_eight_devices():
    _run("""
        import jax
        from repro.launch.mesh import make_host_mesh
        assert dict(make_host_mesh(2, "ens_pp", pp_stages=4).shape) == {
            "ens": 2, "pipe": 4}
        assert dict(make_host_mesh(2, "ens_dp_pp", pp_stages=2).shape) == {
            "ens": 2, "data": 2, "pipe": 2}
        # model axis takes the largest divisor of the remainder (was a
        # hard-coded 2-or-1): 8 devices / ens 2 -> model 4, data 1
        assert dict(make_host_mesh(2, "ens_dp_mp").shape) == {
            "ens": 2, "data": 1, "model": 4}
        # population must divide over the explicit ens axis
        try:
            make_host_mesh(3, "ens_pp", mesh_shape=(2, 4))
            raise SystemExit("3 members cannot divide over ens=2")
        except ValueError as e:
            assert "population" in str(e)
        try:
            make_host_mesh(2, "ens_pp", pp_stages=3)
            raise SystemExit("3 does not divide 4")
        except ValueError as e:
            assert "pp_stages" in str(e)
        print("OK auto fill")
    """)


# ---------------------------------------------------------------------------
# multi-device execution (subprocess, forced 8-device host)
# ---------------------------------------------------------------------------

_TOY = """
        import jax, jax.numpy as jnp
        import numpy as np
        from jax import lax
        from repro.configs.base import TrainConfig
        from jax.sharding import AxisType
        from repro.core.mixing import MixingConfig
        from repro.train import (
            StageFns, train_population_pipelined, train_population_sharded,
        )

        L, DIN, D, DOUT, B = 4, 16, 8, 4, 8

        def init(k):
            ks = jax.random.split(k, 3)
            return {"embed": {"w": jax.random.normal(ks[0], (DIN, D)) * 0.3},
                    "blocks": {"w1": jax.random.normal(ks[1], (L, D, D)) * 0.3},
                    "head": {"w": jax.random.normal(ks[2], (D, DOUT)) * 0.3}}

        def embed_fn(p, b):
            return b["x"] @ p["embed"]["w"]

        def blocks_fn(p, x):
            def body(h, wl):
                return jnp.tanh(h @ wl) + h, None
            h, _ = lax.scan(body, x, p["blocks"]["w1"])
            return h

        def head_fn(p, x, b):
            return jnp.mean((x @ p["head"]["w"] - b["y"]) ** 2)

        def loss_fn(p, b):
            return head_fn(p, blocks_fn(p, embed_fn(p, b)), b)

        def data_fn(m, step, k):
            kx, ky = jax.random.split(k)
            return {"x": jax.random.normal(kx, (B, DIN)),
                    "y": jax.random.normal(ky, (B, DOUT))}

        FNS = StageFns(embed_fn, blocks_fn, head_fn)
        KEY = jax.random.key(0)
        TCFG = TrainConfig(population=2, optimizer="sgd", lr=0.05,
                           total_steps=6, batch_size=B, seq_len=1, seed=0)
"""


@pytest.mark.slow
def test_pipelined_engine_s1_m1_delegates_bitwise():
    """(E,1,1) pipeline mesh, one microbatch: the pipelined entry point
    composes the stage fns and delegates to the fused engine — params,
    losses, and comm all bitwise-equal."""
    _run(_TOY + """
        for kind, kw in [("none", {}), ("papa", {"papa_every": 2}),
                         ("wash", {"base_p": 0.5})]:
            mcfg = MixingConfig(kind=kind, mode="bucketed", **kw)
            ref = train_population_sharded(
                KEY, init, loss_fn, data_fn, TCFG, mcfg, L, record_every=3,
                mesh=jax.make_mesh((2,), ("ens",),
                                   axis_types=(AxisType.Auto,)))
            res = train_population_pipelined(
                KEY, init, FNS, data_fn, TCFG, mcfg, L, record_every=3,
                mesh=jax.make_mesh((2, 1, 1), ("ens", "data", "pipe"),
                                   axis_types=(AxisType.Auto,) * 3),
                microbatches=1)
            for a, b in zip(jax.tree_util.tree_leaves(ref.population),
                            jax.tree_util.tree_leaves(res.population)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert ref.history["loss"] == res.history["loss"], kind
            assert ref.comm_scalars == res.comm_scalars, kind
            print("OK delegation", kind)
    """)


@pytest.mark.slow
def test_pipelined_engine_s4_m4_matches_to_tolerance():
    """S=4 stages, M=4 microbatches: the GPipe schedule's mean-of-means
    loss and accumulated grads match the single-shot engine to float32
    tolerance; WASH comm never exceeds the single-stage plan's."""
    _run(_TOY + """
        mesh4 = jax.make_mesh((2, 4), ("ens", "pipe"),
                              axis_types=(AxisType.Auto,) * 2)
        for kind, kw in [("none", {}), ("wash", {"base_p": 0.5})]:
            mcfg = MixingConfig(kind=kind, mode="bucketed", **kw)
            ref = train_population_sharded(
                KEY, init, loss_fn, data_fn, TCFG, mcfg, L, record_every=3,
                mesh=jax.make_mesh((2,), ("ens",),
                                   axis_types=(AxisType.Auto,)))
            res = train_population_pipelined(
                KEY, init, FNS, data_fn, TCFG, mcfg, L, record_every=3,
                mesh=mesh4, microbatches=4)
            if kind == "none":
                for a, b in zip(jax.tree_util.tree_leaves(ref.population),
                                jax.tree_util.tree_leaves(res.population)):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=2e-5, atol=2e-6)
                np.testing.assert_allclose(ref.history["loss"],
                                           res.history["loss"], rtol=2e-5)
            else:
                # plans differ per stage; the contract is the accounting
                assert 0 < res.comm_scalars <= ref.comm_scalars
                assert all(np.isfinite(v) for v in res.history["loss"])
            print("OK s4m4", kind, ref.comm_scalars, res.comm_scalars)
    """)


@pytest.mark.slow
def test_pipelined_engine_rejects_uneven_split():
    _run(_TOY + """
        mcfg = MixingConfig(kind="none", mode="bucketed")
        mesh = jax.make_mesh((2, 4), ("ens", "pipe"),
                             axis_types=(AxisType.Auto,) * 2)
        try:
            train_population_pipelined(
                KEY, init, FNS, data_fn, TCFG, mcfg, 4, record_every=3,
                mesh=mesh, microbatches=3)
            raise SystemExit("batch 8 does not split into 3")
        except ValueError as e:
            assert "microbatches" in str(e)
        def init6(k):
            p = init(k)
            w = p["blocks"]["w1"]
            p["blocks"]["w1"] = jnp.concatenate([w, w[:2]], axis=0)
            return p
        try:
            train_population_pipelined(
                KEY, init6, FNS, data_fn, TCFG, mcfg, 6, record_every=3,
                mesh=mesh, microbatches=1)
            raise SystemExit("6 layers over 4 stages must fail")
        except ValueError as e:
            assert "evenly" in str(e)
        print("OK rejections")
    """)


@pytest.mark.slow
def test_staged_decode_bitwise_and_traces():
    """Stage-split decode on a (pipe=4) mesh: tokens bitwise-equal to the
    unstaged engine (greedy and temperature), one decode trace per shape,
    degenerate pipe=1 mesh serves unstaged."""
    _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs.base import ModelConfig
        from jax.sharding import AxisType
        from repro.models import transformer as M
        from repro.serving import engine as E

        cfg = ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=4,
                          num_heads=4, num_kv_heads=2, vocab_size=64,
                          max_position=128)
        params = M.init_params(jax.random.key(0), cfg)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 6), 0, 64)}
        mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))

        ref = E.generate(params, cfg, batch, 8)
        out = E.generate(params, cfg, batch, 8, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))

        rt = E.generate(params, cfg, batch, 8, temperature=0.8,
                        key=jax.random.key(7))
        st = E.generate(params, cfg, batch, 8, temperature=0.8,
                        key=jax.random.key(7), mesh=mesh)
        np.testing.assert_array_equal(np.asarray(rt), np.asarray(st))

        out1 = E.generate(params, cfg, batch, 8,
                          mesh=jax.make_mesh((1,), ("pipe",),
                                             axis_types=(AxisType.Auto,)))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(out1))

        E.reset_trace_counts(); E.clear_executable_cache()
        E.generate(params, cfg, batch, 8, mesh=mesh)
        E.generate(params, cfg, batch, 8, mesh=mesh)
        assert E.decode_trace_count() == 1 and E.prefill_trace_count() == 1
        assert E.executable_cache_size() == 1

        # MLA cache (ckv/krope leaves) stage-splits too
        cfg_mla = ModelConfig(name="tinymla", d_model=32, d_ff=64,
                              num_layers=4, num_heads=4, num_kv_heads=4,
                              vocab_size=64, max_position=128, mla=True,
                              kv_lora_rank=8, qk_rope_dim=4, qk_nope_dim=4,
                              v_head_dim=8)
        pm = M.init_params(jax.random.key(2), cfg_mla)
        r = E.generate(pm, cfg_mla, batch, 6)
        s = E.generate(pm, cfg_mla, batch, 6, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(s))
        print("OK staged decode")
    """)


@pytest.mark.slow
def test_hlo_collectives_stay_in_stage_rings():
    """On an (ens=2, pipe=4) mesh (device id = e*4 + p):

      * the shard-local WASH mixer's collective-permutes are ens-ring
        hops INSIDE a stage ring — src % 4 == tgt % 4 for every pair;
      * the staged decode program's permutes move the activation exactly
        one stage forward — tgt == src + 1, never wrapping.
    """
    _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.analysis import contracts
        from jax.sharding import AxisType
        from repro.core.mixing import MixingConfig
        from repro.core import shardplan

        mesh = jax.make_mesh((2, 4), ("ens", "pipe"),
                             axis_types=(AxisType.Auto,) * 2)
        L, D = 8, 16
        pop_sds = {"blocks": {"w": jax.ShapeDtypeStruct((2, L, D),
                                                        jnp.float32)}}
        pop_specs = {"blocks": {"w": P("ens", "pipe", None)}}
        opt_sds = {"step": jax.ShapeDtypeStruct((2,), jnp.int32)}
        opt_specs = {"step": P("ens")}
        key_sds = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        mixer = shardplan.make_shardlocal_mixer(mesh, mcfg, L, pop_specs,
                                                opt_specs)
        rep = contracts.lower_and_check(
            jax.jit(mixer), (pop_sds, opt_sds, key_sds),
            contracts.Contract(
                name="wash-mixer-rings",
                require_collectives=("collective-permute",),
                permute_rules=(contracts.stage_ring(4),),
            ))
        print("OK mixer rings", rep.permute_pairs)

        from repro.configs.base import ModelConfig
        from repro.models import transformer as M
        from repro.serving import engine as E
        cfg = ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=4,
                          num_heads=4, num_kv_heads=2, vocab_size=64,
                          max_position=128)
        params_sds = jax.eval_shape(
            lambda: M.init_params(jax.random.key(0), cfg))
        pmesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
        E.clear_executable_cache()
        _, decode = E._programs(cfg, False, 2, 4, 8, 16, True, pmesh,
                                stages=4, params=params_sds)
        cache_sds = jax.eval_shape(lambda: M.init_cache(cfg, 2, 16))
        rep2 = contracts.lower_and_check(
            decode,
            (params_sds,
             jax.ShapeDtypeStruct((2, 4), jnp.int32),
             cache_sds,
             jax.ShapeDtypeStruct((2, 1, 64), jnp.float32),
             jax.ShapeDtypeStruct((2,), jax.random.key(0).dtype),
             jax.ShapeDtypeStruct((), jnp.float32)),
            contracts.Contract(
                name="staged-decode-hops",
                require_collectives=("collective-permute",),
                permute_rules=(contracts.forward_hop(4),),
            ))
        print("OK decode hops", rep2.permute_pairs)
    """)
