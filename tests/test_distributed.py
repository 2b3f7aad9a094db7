"""Distributed paths that need >1 device run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count (jax locks the device
count at first init, so the main pytest process must stay at 1 device).
"""

import os
import subprocess
import sys
import textwrap

import pytest

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


def test_collective_shuffle_equals_stacked_reference():
    out = _run(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import population as pop
        from jax.sharding import AxisType
        from repro.core.mixing import MixingConfig, mix_stacked, mix_collective
        from repro.core.layer_index import infer_layer_ids, total_layers

        key = jax.random.key(0)
        def init(k):
            ks = jax.random.split(k, 6)
            return {"embed": {"w": jax.random.normal(ks[0], (64, 32))},
                    "blocks": [{"w1": jax.random.normal(ks[1+i], (32, 32))} for i in range(3)],
                    "head": {"w": jax.random.normal(ks[5], (32, 8))}}
        N = 4
        stacked = pop.init_population(init, key, N, same_init=False)
        lids = infer_layer_ids(pop.member(stacked, 0), 3)
        L = total_layers(3)
        cfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        ref, _, comm_ref = mix_stacked(1, key, stacked, None, cfg, lids, L)

        mesh = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        def member_fn(params):
            params = jax.tree_util.tree_map(lambda x: x[0], params)
            out, _, comm = mix_collective(1, key, params, None, cfg, lids, L, "ens")
            return jax.tree_util.tree_map(lambda x: x[None], out), comm[None]
        specs = jax.tree_util.tree_map(lambda x: P("ens"), stacked)
        f = jax.shard_map(member_fn, mesh=mesh, in_specs=(specs,),
                      out_specs=(specs, P("ens")))
        out, comm = jax.jit(f)(stacked)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)))
        assert err == 0.0, err
        assert float(comm[0]) == float(comm_ref), (comm, comm_ref)
        print("OK collective == stacked, comm", float(comm_ref))
        """
    )
    assert "OK" in out


def test_pjit_sharded_population_wash_step_runs():
    """Stacked population sharded over an ens mesh axis: the bucketed
    shuffle (jnp.roll over the sharded axis) must lower to collective
    permutes and produce the same result as the single-device run."""
    out = _run(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import population as pop
        from repro.core.mixing import MixingConfig, mix_once
        from repro.core.layer_index import infer_layer_ids, total_layers

        key = jax.random.key(0)
        def init(k):
            return {"embed": {"w": jax.random.normal(k, (64, 32))},
                    "blocks": [{"w1": jax.random.normal(k, (32, 32))}],
                    "head": {"w": jax.random.normal(k, (32, 8))}}
        N = 4
        stacked = pop.init_population(init, key, N, same_init=False)
        lids = infer_layer_ids(pop.member(stacked, 0), 1)
        L = total_layers(1)
        cfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
        ref, _, _ = mix_once(key, stacked, None, cfg, lids, L)

        from jax.sharding import AxisType
        mesh = jax.make_mesh((4, 2), ("ens", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        sh = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("ens"))), stacked)
        step = jax.jit(lambda p: mix_once(key, p, None, cfg, lids, L)[0])
        lowered = step.lower(sh)
        txt = lowered.compile().as_text()
        assert ("collective-permute" in txt) or ("all-to-all" in txt), "no permute collective found"
        out = step(sh)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(ref)))
        assert err == 0.0, err
        print("OK pjit wash step, collective-permute present")
        """
    )
    assert "OK" in out


def test_mesh_constructors():
    out = _run(
        """
        from repro.launch.mesh import make_production_mesh, make_ensemble_mesh, data_axes
        m = make_production_mesh()
        assert dict(m.shape) == {"data": 16, "model": 16}, m.shape
        mp = make_production_mesh(multi_pod=True)
        assert dict(mp.shape) == {"pod": 2, "data": 16, "model": 16}
        assert data_axes(mp) == ("pod", "data")
        e = make_ensemble_mesh(4)
        assert dict(e.shape) == {"ens": 4, "data": 4, "model": 16}
        e2 = make_ensemble_mesh(2, multi_pod=True)
        assert dict(e2.shape) == {"ens": 2, "data": 16, "model": 16}
        print("OK meshes")
        """,
        devices=512,
    )
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_cli_one_pair():
    """The dry-run CLI end-to-end on the cheapest (arch, shape) pair."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "hymba-1.5b", "--shape", "decode_32k",
         "--out-dir", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]
    assert "[ok]" in r.stdout


@pytest.mark.slow
def test_fused_engine_multidevice_matches_reference():
    """The fused shard_map engine on a real 4-device ens mesh (one member
    per device → every WASH bucket is a genuine ppermute) must match the
    single-device vmap reference loop: params bitwise for WASH, identical
    comm accounting, and the compiled step must contain collective-permute."""
    out = _run(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import TrainConfig
        from jax.sharding import AxisType
        from repro.core import shuffle as shf
        from repro.core.mixing import MixingConfig
        from repro.train import train_population
        from repro.train.engine import train_population_sharded

        KEY = jax.random.key(0)
        def init(k):
            ks = jax.random.split(k, 3)
            return {"embed": {"w": jax.random.normal(ks[0], (16, 8))},
                    "blocks": [{"w1": jax.random.normal(ks[1], (8, 8))}],
                    "head": {"w": jax.random.normal(ks[2], (8, 4))}}
        def data_fn(m, step, k):
            return {"x": jax.random.normal(k, (4, 16)),
                    "y": jax.random.normal(jax.random.fold_in(k, 1), (4, 4))}
        def loss_fn(p, b):
            h = jnp.tanh(b["x"] @ p["embed"]["w"] @ p["blocks"][0]["w1"])
            return jnp.mean((h @ p["head"]["w"] - b["y"]) ** 2)

        for kind in ("wash", "wash_opt"):
            tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05,
                               total_steps=11, batch_size=4)
            mcfg = MixingConfig(kind=kind, base_p=0.5, mode="bucketed")
            ref = train_population(KEY, init, loss_fn, data_fn, tcfg, mcfg, 1,
                                   record_every=5)
            fused = train_population_sharded(KEY, init, loss_fn, data_fn,
                                             tcfg, mcfg, 1, record_every=5)
            err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree_util.tree_leaves(ref.population),
                jax.tree_util.tree_leaves(fused.population)))
            assert err == 0.0, (kind, err)
            assert ref.comm_scalars == fused.comm_scalars

        # blocked collective shuffle == stacked roll on every block size
        key = jax.random.key(7)
        n, D = 4, 37
        x = jax.random.normal(key, (n, D))
        idx = shf.bucketed_plan(jax.random.fold_in(key, 1), D, n, 0.8)
        stacked = shf.bucketed_apply_stacked(x, idx)
        for m in (4, 2, 1):
            mesh = jax.make_mesh((m,), ("ens",), axis_types=(AxisType.Auto,))
            f = jax.shard_map(
                lambda xb: shf.bucketed_apply_collective_blocked(xb, idx, "ens"),
                mesh=mesh, in_specs=(P("ens"),), out_specs=P("ens"),
                check_vma=False)
            err = float(jnp.max(jnp.abs(jax.jit(f)(x) - stacked)))
            assert err == 0.0, (m, err)
        mesh = jax.make_mesh((4,), ("ens",), axis_types=(AxisType.Auto,))
        f = jax.shard_map(
            lambda xb: shf.bucketed_apply_collective_blocked(xb, idx, "ens"),
            mesh=mesh, in_specs=(P("ens"),), out_specs=P("ens"),
            check_vma=False)
        txt = jax.jit(f).lower(x).compile().as_text()
        assert "collective-permute" in txt, "fused shuffle did not lower to ppermute"
        print("OK fused engine multidevice")
        """,
        devices=4,
    )
    assert "OK" in out


def test_shardlocal_mixer_preserves_consensus_distance():
    """§Perf shard-local shuffle: per-shard bucketed plans under shard_map
    must still be exact permutations (Eq. 5) and actually mix."""
    out = _run(
        """
        import os, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig, InputShape
        from repro.core.consensus import sq_distance_to_consensus
        from repro.core.mixing import MixingConfig
        from repro.launch.dryrun import make_shardlocal_mixer
        from repro.core import population as pop

        cfg = ModelConfig(num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
                          d_ff=64, vocab_size=64, dtype="float32")
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 2, 2), ("ens", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        key = jax.random.key(0)
        def init(k):
            return {"embed": {"w": jax.random.normal(k, (64, 32))},
                    "blocks": {"w1": jax.random.normal(k, (2, 32, 64))},
                    "head": {"w": jax.random.normal(k, (32, 8))}}
        stacked = pop.init_population(init, key, 2, same_init=False)
        pop_specs = jax.tree_util.tree_map(lambda x: P("ens"), stacked)
        opt = {"mu": jax.tree_util.tree_map(jnp.zeros_like, stacked),
               "step": jnp.zeros((2,), jnp.int32)}
        opt_specs = {"mu": pop_specs, "step": P("ens")}
        mcfg = MixingConfig(kind="wash_opt", base_p=0.5, mode="bucketed")
        mixer = make_shardlocal_mixer(cfg, mcfg, mesh, pop_specs, opt_specs)
        sh = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("ens"))), stacked)
        sho = {"mu": jax.tree_util.tree_map(
                   lambda x: jax.device_put(x, NamedSharding(mesh, P("ens"))), opt["mu"]),
               "step": jax.device_put(opt["step"], NamedSharding(mesh, P("ens")))}
        out, opt2, comm = jax.jit(mixer)(sh, sho, key)
        d0 = float(sq_distance_to_consensus(stacked))
        d1 = float(sq_distance_to_consensus(out))
        assert abs(d0 - d1) / d0 < 1e-5, (d0, d1)
        moved = sum(float(jnp.sum(a != b)) for a, b in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(stacked)))
        assert moved > 0, "shuffle was a no-op"
        assert float(comm) > 0
        # per-coordinate multiset preserved (values only move between members)
        import numpy as np
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(stacked)):
            np.testing.assert_allclose(np.sort(np.asarray(a), 0),
                                       np.sort(np.asarray(b), 0), rtol=1e-6)
        print("OK shard-local mixer")
        """
    )
    assert "OK" in out
