"""Shard-local mixing planner (repro.core.shardplan) + the fused engine
on ens×data×model meshes.

Host-side planner logic (axis classification, per-shard budgets, comm
volumes) runs in-process; everything that needs >1 device runs in a
subprocess with a forced 8-device CPU host (jax locks the device count at
first init), following tests/test_distributed.py.

Contracts asserted here:
  * the fused engine on an (ens=2, data=2, model=2) mesh is bitwise-equal
    to the ens-only engine for a replicated-model config, for all four
    mixing modes, with identical exact comm accounting and ≤ 2 chunk
    traces per run;
  * shard-local plans draw independent permutations per (data, model)
    shard coordinate (the plan-key fold), while unsharded leaves reproduce
    the global plan bitwise;
  * per-shard static comm volumes sum to ≤ the global-plan volume
    (equality when nothing is sharded);
  * launch/dryrun's --shard-local path is a delegator to core/shardplan
    (identical HLO collective footprint).
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

from repro.core import shardplan, shuffle as shf
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import MixingConfig, static_mix_comm

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
    """The planner only reads axis names + sizes; no devices needed."""
    return types.SimpleNamespace(axis_names=tuple(shape), shape=shape)


MEMBER = {
    "embed": {"w": jax.ShapeDtypeStruct((32, 16), jnp.float32)},
    "blocks": {"w1": jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)},
    "head": {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)},
}
SPECS = {
    "embed": {"w": P(None, "model")},
    "blocks": {"w1": P(None, None, "model")},
    "head": {"w": P(None, "model")},
}
REPL = jax.tree_util.tree_map(
    lambda _: P(), MEMBER, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
)


def _plan(mesh, specs, n=4, kind="wash", base_p=0.5, **kw):
    mcfg = MixingConfig(kind=kind, base_p=base_p, mode="bucketed", **kw)
    lids = infer_layer_ids(MEMBER, 2)
    return shardplan.plan_population_mixing(
        mesh, MEMBER, specs, mcfg, lids, total_layers(2), n
    )


# ---------------------------------------------------------------------------
# host-side planner logic (fast, 1 device)
# ---------------------------------------------------------------------------


def test_classify_axes():
    cl = shardplan.classify_axes
    # population divides over ens×data -> data absorbed into the population
    assert cl(fake_mesh(ens=2, data=2, model=2), 4) == (("ens", "data"), ())
    assert cl(fake_mesh(ens=2, pod=2, data=2, model=4), 8) == (
        ("ens", "pod", "data"), ())
    # otherwise data splits each member's batch
    assert cl(fake_mesh(ens=2, data=2, model=2), 2) == (("ens",), ("data",))
    assert cl(fake_mesh(ens=4, data=4, model=16), 4) == (("ens",), ("data",))
    # degenerate axes drop out entirely
    assert cl(fake_mesh(ens=1, data=1, model=1), 4) == (("ens",), ())
    assert cl(fake_mesh(ens=4), 4) == (("ens",), ())
    with pytest.raises(ValueError, match="ens"):
        cl(fake_mesh(data=2), 2)
    with pytest.raises(ValueError, match="divide"):
        cl(fake_mesh(ens=3), 4)


def test_local_shard_shapes_via_spec_slicing():
    pplan = _plan(fake_mesh(ens=2, data=2, model=2), SPECS, n=4)
    by_index = {i.index: i for i in pplan.infos}
    flat, _ = jax.tree_util.tree_flatten_with_path(MEMBER)
    for idx, (path, leaf) in enumerate(flat):
        info = by_index[idx]
        assert info.member_shape == leaf.shape
        if info.sharded_dims:
            (dim, axis, lsz), = info.sharded_dims
            assert axis == "model" and lsz == leaf.shape[dim] // 2
            assert info.local_shape[dim] == lsz
            assert info.num_shards == 2
        else:
            assert info.local_shape == leaf.shape
    # the scanned layer axis is never sharded
    blocks = [i for i in pplan.infos if i.layered]
    assert len(blocks) == 1 and blocks[0].local_shape[0] == 2


def test_planner_rejects_population_axes_in_member_specs():
    bad = {**SPECS, "head": {"w": P(None, "ens")}}
    with pytest.raises(ValueError, match="population"):
        _plan(fake_mesh(ens=2, data=2, model=2), bad, n=4)


def test_shard_volumes_sum_at_most_global():
    """Per-shard exact volumes sum to ≤ the global-plan volume — by
    construction (each shard draws floor(global_budget / num_shards)), and
    exactly equal when nothing is sharded."""
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    lids = infer_layer_ids(MEMBER, 2)
    tl = total_layers(2)
    global_comm = static_mix_comm(MEMBER, mcfg, lids, tl, 4)

    sharded = _plan(fake_mesh(ens=2, data=2, model=2), SPECS, n=4)
    assert shardplan.static_shard_mix_comm(sharded) <= global_comm
    assert shardplan.static_shard_mix_comm(sharded) > 0
    # per-leaf: num_shards * per-shard sent <= the unsharded leaf's sent
    repl = _plan(fake_mesh(ens=2, data=2, model=2), REPL, n=4)
    vol_sharded = shardplan.shard_leaf_volumes(sharded)
    vol_global = shardplan.shard_leaf_volumes(repl)
    for idx, (sent, num) in vol_sharded.items():
        g_sent, g_num = vol_global[idx]
        assert g_num == 1
        assert sent * num <= g_sent

    # unsharded plan reproduces the global accounting exactly
    assert shardplan.static_shard_mix_comm(repl) == global_comm
    # PAPA moves the full member either way
    papa_s = _plan(fake_mesh(ens=2, data=2, model=2), SPECS, n=4, kind="papa")
    papa_r = _plan(fake_mesh(ens=2, data=2, model=2), REPL, n=4, kind="papa")
    d = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(MEMBER))
    assert shardplan.static_shard_mix_comm(papa_s) == d
    assert shardplan.static_shard_mix_comm(papa_r) == d


def test_stage_volumes_sum_to_the_plan_total():
    """Pipeline accounting: per-stage exact volumes are a PARTITION of the
    pipe-plan's global volume — ``static_shard_mix_comm`` reports their
    literal float64 sum, so equality holds to the last ulp."""
    from repro.sharding import rules

    lids = infer_layer_ids(MEMBER, 2)
    staged = rules.stage_member_specs(REPL, lids, "pipe")
    mesh = fake_mesh(ens=2, data=1, pipe=2)
    for kind in ("wash", "papa"):
        pplan = _plan(mesh, staged, n=4, kind=kind)
        assert pplan.num_stages == 2
        per_stage = [shardplan.static_stage_mix_comm(pplan, s)
                     for s in range(2)]
        total = shardplan.static_shard_mix_comm(pplan)
        assert sum(per_stage) == total, (kind, per_stage, total)
        assert all(v >= 0 for v in per_stage) and total > 0
        # never more than the single-stage plan moves
        single = _plan(fake_mesh(ens=2), REPL, n=4, kind=kind)
        assert total <= shardplan.static_shard_mix_comm(single) + 1e-6
    with pytest.raises(ValueError, match="stage"):
        shardplan.static_stage_mix_comm(pplan, 2)
    # a single-stage plan: stage 0 IS the whole plan
    single = _plan(fake_mesh(ens=2), REPL, n=4)
    assert single.num_stages == 1
    assert shardplan.static_stage_mix_comm(single, 0) == \
        shardplan.static_shard_mix_comm(single)


def test_unsharded_plans_match_global_plan_bitwise():
    """With no sharded leaf the builder must reproduce shf.make_plan
    exactly (same per-leaf key folds, same budgets) — this is what makes
    the multi-axis engine bitwise-recover the ens-only path."""
    pplan = _plan(fake_mesh(ens=2, data=2, model=2), REPL, n=4)
    key = jax.random.key(7)
    lids = infer_layer_ids(MEMBER, 2)
    ref = shf.make_plan(key, MEMBER, lids, total_layers(2), 0.5,
                        "decreasing", mode="bucketed", n=4)
    got = shardplan.build_local_plans(key, pplan)
    ref_l = jax.tree_util.tree_leaves(ref, is_leaf=lambda x: x is None)
    got_l = jax.tree_util.tree_leaves(got, is_leaf=lambda x: x is None)
    assert len(ref_l) == len(got_l)
    for a, b in zip(ref_l, got_l):
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_make_host_mesh_clamps_to_device_count():
    from repro.launch.mesh import make_host_mesh

    # 1-device main pytest process: every kind degenerates
    assert dict(make_host_mesh(4, "ens").shape) == {"ens": 1}
    assert dict(make_host_mesh(4, "ens_dp").shape) == {"ens": 1, "data": 1}
    assert dict(make_host_mesh(4, "ens_dp_mp").shape) == {
        "ens": 1, "data": 1, "model": 1}
    with pytest.raises(ValueError, match="kind"):
        make_host_mesh(4, "nope")


def test_engine_accepts_degenerate_3d_mesh_bitwise():
    """The multi-axis engine body on a (1,1,1) mesh must reproduce the
    default 1-device ens-only run bitwise (the shardplan mixing path vs
    mix_collective_blocked)."""
    from conftest import tiny_data_fn, tiny_init, tiny_loss_fn
    from repro.configs.base import TrainConfig
    from jax.sharding import AxisType
    from repro.train.engine import train_population_sharded

    key = jax.random.key(0)
    tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05, total_steps=7,
                       batch_size=4)
    for kind, kw in [("wash_opt", dict(base_p=0.5)),
                     ("papa", dict(papa_every=3))]:
        mcfg = MixingConfig(kind=kind, mode="bucketed", **kw)
        ref = train_population_sharded(
            key, tiny_init, tiny_loss_fn, tiny_data_fn, tcfg, mcfg, 2,
            record_every=3,
        )
        mesh = jax.make_mesh((1, 1, 1), ("ens", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        got = train_population_sharded(
            key, tiny_init, tiny_loss_fn, tiny_data_fn, tcfg, mcfg, 2,
            record_every=3, mesh=mesh,
        )
        for a, b in zip(jax.tree_util.tree_leaves(ref.population),
                        jax.tree_util.tree_leaves(got.population)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ref.comm_scalars == got.comm_scalars
        assert ref.history["loss"] == got.history["loss"]


def test_param_specs_rejected_on_ens_only_mesh():
    from conftest import tiny_data_fn, tiny_init, tiny_loss_fn
    from repro.configs.base import TrainConfig
    from repro.train.engine import train_population_sharded

    tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.05, total_steps=2,
                       batch_size=4)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    with pytest.raises(ValueError, match="multi-axis"):
        train_population_sharded(
            jax.random.key(0), tiny_init, tiny_loss_fn, tiny_data_fn,
            tcfg, mcfg, 2, param_specs={"anything": P()},
        )


# ---------------------------------------------------------------------------
# multi-device execution (subprocess, forced 8-device host)
# ---------------------------------------------------------------------------

_COMMON = """
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import TrainConfig
        from jax.sharding import AxisType
        from repro.core.mixing import MixingConfig
        from repro.train import engine as engine_mod
        from repro.train.engine import train_population_sharded

        KEY = jax.random.key(0)

        def init(k):
            ks = jax.random.split(k, 4)
            return {"embed": {"w": jax.random.normal(ks[0], (16, 8))},
                    "blocks": [{"w1": jax.random.normal(ks[1], (8, 8))},
                               {"w1": jax.random.normal(ks[2], (8, 8))}],
                    "head": {"w": jax.random.normal(ks[3], (8, 4))}}

        def data_fn(m, step, k):
            return {"x": jax.random.normal(k, (4, 16)),
                    "y": jax.random.normal(jax.random.fold_in(k, 1), (4, 4))}

        def loss_fn(p, b):
            h = b["x"] @ p["embed"]["w"]
            for blk in p["blocks"]:
                h = jnp.tanh(h @ blk["w1"])
            return jnp.mean((h @ p["head"]["w"] - b["y"]) ** 2)

        SPECS = {"embed": {"w": P(None, "model")},
                 "blocks": [{"w1": P(None, "model")}, {"w1": P(None, "model")}],
                 "head": {"w": P(None, "model")}}

        def leaves_np(tree):
            return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]

        MESH3 = jax.make_mesh((2, 2, 2), ("ens", "data", "model"),
                              axis_types=(AxisType.Auto,) * 3)
"""


@pytest.mark.slow
def test_3d_mesh_bitwise_parity_all_mixing_modes():
    """The acceptance contract: fused engine on (ens=2, data=2, model=2)
    == the ens-only engine bitwise for a replicated-model config, for all
    4 mixing modes, with identical exact comm accounting and ≤ 2 chunk
    traces per run."""
    out = _run(_COMMON + """
        mesh1 = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        for kind, kw in [("wash", dict(base_p=0.5)),
                         ("wash_opt", dict(base_p=0.5)),
                         ("papa", dict(papa_every=3, papa_alpha=0.9)),
                         ("none", dict())]:
            tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                               total_steps=7, batch_size=4)
            mcfg = MixingConfig(kind=kind, mode="bucketed", **kw)
            ref = train_population_sharded(KEY, init, loss_fn, data_fn,
                                           tcfg, mcfg, 2, record_every=3,
                                           mesh=mesh1)
            engine_mod.reset_chunk_trace_count()
            got = train_population_sharded(KEY, init, loss_fn, data_fn,
                                           tcfg, mcfg, 2, record_every=3,
                                           mesh=MESH3)
            traces = engine_mod.chunk_trace_count()
            assert traces <= 2, (kind, traces)
            for a, b in zip(leaves_np(ref.population),
                            leaves_np(got.population)):
                np.testing.assert_array_equal(a, b)
            assert ref.comm_scalars == got.comm_scalars, kind
            assert ref.history["loss"] == got.history["loss"], kind
            assert ref.history["comm"] == got.history["comm"], kind
            print(f"OK {kind} traces={traces}")
        print("OK all modes")
        """)
    assert "OK all modes" in out


@pytest.mark.slow
def test_3d_mesh_sharded_members():
    """Model-sharded members: elementwise mixing kinds stay bitwise-equal
    to the ens-only engine (gather → grad → slice is exact); WASH draws
    different (shard-local) plans but remains an exact permutation — the
    per-coordinate multiset across members is preserved.  With the
    population not dividing ens×data, batches split over the data axis and
    parity is numeric (mean-of-means), not bitwise."""
    out = _run(_COMMON + """
        mesh1 = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        for kind, kw in [("none", dict()),
                         ("papa", dict(papa_every=3, papa_alpha=0.9))]:
            tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                               total_steps=7, batch_size=4)
            mcfg = MixingConfig(kind=kind, mode="bucketed", **kw)
            ref = train_population_sharded(KEY, init, loss_fn, data_fn,
                                           tcfg, mcfg, 2, record_every=3,
                                           mesh=mesh1)
            got = train_population_sharded(KEY, init, loss_fn, data_fn,
                                           tcfg, mcfg, 2, record_every=3,
                                           mesh=MESH3, param_specs=SPECS)
            for a, b in zip(leaves_np(ref.population),
                            leaves_np(got.population)):
                np.testing.assert_array_equal(a, b)
            print("OK sharded", kind)

        # sharded WASH: exact permutation per shard
        tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                           total_steps=1, batch_size=4)
        mcfg = MixingConfig(kind="wash", mode="bucketed", base_p=0.9)
        ref = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                       mcfg, 2, record_every=1, mesh=mesh1)
        got = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                       mcfg, 2, record_every=1, mesh=MESH3,
                                       param_specs=SPECS)
        moved = 0.0
        for a, b in zip(leaves_np(ref.population), leaves_np(got.population)):
            np.testing.assert_allclose(np.sort(a, 0), np.sort(b, 0), rtol=1e-6)
            moved += float(np.sum(a != b))
        assert moved > 0, "shard-local plans identical to global plans?"
        print("OK sharded wash multiset")

        # dp mode: population 2 on the same mesh -> batches split over data
        tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.05,
                           total_steps=5, batch_size=4)
        mcfg = MixingConfig(kind="wash", mode="bucketed", base_p=0.5)
        mesh2 = jax.make_mesh((2,), ("ens",), axis_types=(AxisType.Auto,))
        ref = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                       mcfg, 2, record_every=2, mesh=mesh2)
        got = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                       mcfg, 2, record_every=2, mesh=MESH3)
        for a, b in zip(leaves_np(ref.population), leaves_np(got.population)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
        print("OK dp mode")
        """)
    assert "OK dp mode" in out


@pytest.mark.slow
def test_shard_plans_fold_position_per_shard():
    """The plan-key fold: a model-sharded leaf draws a different plan on
    each model coordinate (fold_in(leaf_key, shard_pos)), reproducible
    host-side; unsharded leaves fold nothing and agree across chips."""
    out = _run("""
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import shardplan, shuffle as shf
        from jax.sharding import AxisType
        from repro.core.layer_index import infer_layer_ids, total_layers
        from repro.core.mixing import MixingConfig

        mesh = jax.make_mesh((2, 2, 2), ("ens", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        member = {"a": jax.ShapeDtypeStruct((32, 16), jnp.float32),
                  "b": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
        specs = {"a": P(None, "model"), "b": P()}
        mcfg = MixingConfig(kind="wash", base_p=0.8, schedule="constant",
                            mode="bucketed")
        lids = infer_layer_ids(member, 1)
        tl = total_layers(1)
        pplan = shardplan.plan_population_mixing(
            mesh, member, specs, mcfg, lids, tl, 4)
        key = jax.random.key(3)

        def probe():
            plans = shardplan.build_local_plans(key, pplan)
            return {k: v[None] for k, v in plans.items() if v is not None}

        f = jax.shard_map(probe, mesh=mesh, in_specs=(),
                      out_specs={"a": P("model"), "b": P("model")},
                      check_vma=False)
        per_shard = jax.jit(f)()
        a0, a1 = np.asarray(per_shard["a"][0]), np.asarray(per_shard["a"][1])
        assert not np.array_equal(a0, a1), "sharded leaf plans must differ"

        # host-side reproduction of each shard's plan from the key fold
        infos = {i.index: i for i in pplan.infos}
        flat_keys = list(member)  # dict order == flatten order
        ia = infos[flat_keys.index("a")]
        for pos, got in ((0, a0), (1, a1)):
            k = jax.random.fold_in(key, ia.index)
            k = jax.random.fold_in(k, jnp.asarray(pos, jnp.int32))
            exp = shf.bucketed_plan(k, ia.d_local, 4, 0.0, k_per=ia.k_per_local)
            np.testing.assert_array_equal(np.asarray(exp), got)
        # unsharded leaf: all chips drew the identical (global) plan
        b0, b1 = np.asarray(per_shard["b"][0]), np.asarray(per_shard["b"][1])
        np.testing.assert_array_equal(b0, b1)
        ref = shf.make_plan(key, member, lids, tl, 0.8, "constant",
                            mode="bucketed", n=4)
        np.testing.assert_array_equal(np.asarray(ref["b"]), b0)
        print("OK plan-key fold")
        """)
    assert "OK plan-key fold" in out


@pytest.mark.slow
def test_checkpoint_roundtrip_sharded_population():
    """checkpoint.save on the fused engine's multi-device sharded output:
    leaves are explicitly gathered (no error, no silent implicit
    transfer), and restore round-trips bitwise."""
    out = _run(_COMMON + """
        import os, tempfile
        from repro.train import checkpoint

        mesh = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                           total_steps=4, batch_size=4)
        mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        res = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                       mcfg, 2, record_every=2, mesh=mesh)
        for leaf in jax.tree_util.tree_leaves(res.population):
            assert len(leaf.sharding.device_set) > 1, "not actually sharded"
        path = os.path.join(tempfile.mkdtemp(), "pop")
        written = checkpoint.save(path, res.population)
        like = jax.tree_util.tree_map(np.asarray, res.population)
        back = checkpoint.restore(written, like)
        for a, b in zip(leaves_np(res.population), leaves_np(back)):
            np.testing.assert_array_equal(a, b)
        # restore with the sharded population as `like`: leaves come back
        # as committed device arrays on the ORIGINAL multi-device sharding
        # (not host numpy), so re-feeding the fused engine costs no
        # per-step implicit transfer.
        back_dev = checkpoint.restore(written, res.population)
        for a, b in zip(jax.tree_util.tree_leaves(res.population),
                        jax.tree_util.tree_leaves(back_dev)):
            assert isinstance(b, jax.Array)
            assert b.sharding == a.sharding
            assert len(b.sharding.device_set) > 1
        for a, b in zip(leaves_np(res.population), leaves_np(back_dev)):
            np.testing.assert_array_equal(a, b)
        print("OK checkpoint roundtrip")
        """)
    assert "OK checkpoint roundtrip" in out


@pytest.mark.slow
def test_averaged_params_runs_mean_before_gather():
    """serving.averaged_params on the fused engine's sharded population:
    the ens-mean runs on the sharded arrays first (the gathered result is
    one member's size, not N×), and the soup equals the vmap engine's
    bitwise."""
    out = _run(_COMMON + """
        from repro.core import averaging
        from repro.serving import averaged_params
        from repro.train import train_population

        tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                           total_steps=6, batch_size=4)
        mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        ref = train_population(KEY, init, loss_fn, data_fn, tcfg, mcfg, 2,
                               record_every=3)
        mesh4 = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        fused = train_population_sharded(KEY, init, loss_fn, data_fn, tcfg,
                                         mcfg, 2, record_every=3, mesh=mesh4)
        for leaf in jax.tree_util.tree_leaves(fused.population):
            assert len(leaf.sharding.device_set) > 1

        # the mean itself executes on the sharded population: its output
        # exists before any host gather and is member-sized (1x moved)
        soup_dev = averaging.uniform_soup(fused.population)
        for leaf, m in zip(jax.tree_util.tree_leaves(soup_dev),
                           jax.tree_util.tree_leaves(ref.population)):
            assert isinstance(leaf, jax.Array)
            assert leaf.shape == m.shape[1:], "ens axis must be averaged out"

        soup = averaged_params(fused)
        soup_ref = averaged_params(ref)
        for a, b in zip(leaves_np(soup), leaves_np(soup_ref)):
            np.testing.assert_array_equal(a, b)
        print("OK serving soup")
        """)
    assert "OK serving soup" in out


@pytest.mark.slow
def test_dryrun_shardlocal_delegates_with_identical_hlo_collectives():
    """launch/dryrun's --shard-local mixer is a thin delegator to
    core/shardplan: both construction paths lower to byte-identical
    collective footprints (analysis.contracts accounting), and the
    shuffle exchanges appear as collective-permute."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis import contracts
        from repro.configs.base import ModelConfig
        from repro.core import population as pop, shardplan
        from jax.sharding import AxisType
        from repro.core.layer_index import infer_layer_ids, total_layers
        from repro.core.mixing import MixingConfig
        from repro.launch.dryrun import make_shardlocal_mixer, params_shapes
        from repro.sharding import rules

        cfg = ModelConfig(num_layers=2, d_model=32, num_heads=2,
                          num_kv_heads=2, d_ff=64, vocab_size=64,
                          dtype="float32")
        mesh = jax.make_mesh((2, 2, 2), ("ens", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        params_sds = params_shapes(cfg)
        pspecs = rules.param_pspecs(params_sds, cfg, mesh)
        add_ens = lambda tree: jax.tree_util.tree_map(
            lambda s: P(*(("ens",) + tuple(s))), tree,
            is_leaf=lambda x: isinstance(x, P))
        pop_specs = add_ens(pspecs)
        opt_specs = {"mu": pop_specs, "step": P("ens")}
        mcfg = MixingConfig(kind="wash_opt", base_p=0.5, mode="bucketed")

        stack = lambda t: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype), t)
        pop_sds = stack(params_sds)
        opt_sds = {"mu": pop_sds,
                   "step": jax.ShapeDtypeStruct((2,), jnp.int32)}
        key_sds = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)

        def lower(mixer):
            return jax.jit(mixer).lower(pop_sds, opt_sds, key_sds).compile()

        via_dryrun = lower(make_shardlocal_mixer(cfg, mcfg, mesh, pop_specs,
                                                 opt_specs))
        via_core = lower(shardplan.make_shardlocal_mixer(
            mesh, mcfg, cfg.num_layers, pop_specs, opt_specs))
        f1 = contracts.collective_footprint(via_dryrun)
        f2 = contracts.collective_footprint(via_core)
        assert f1 == f2, (f1, f2)
        assert f1["counts"]["collective-permute"] > 0, f1
        print("OK delegation, collectives:", f1["bytes"])
        """)
    assert "OK delegation" in out
