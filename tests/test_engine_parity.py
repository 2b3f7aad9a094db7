"""Fused shard_map engine vs the vmap reference loop.

The fused engine (repro.train.engine) must be a drop-in replacement: same
populations (bitwise on the 1-device CPU mesh — the collective blocked
shuffle degenerates to exactly the stacked roll), identical comm
accounting, identical history schedule, for every mixing mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.base import TrainConfig
from repro.core.mixing import MixingConfig
from repro.serving import averaged_params
from repro.train import train_population
from repro.train import engine as engine_mod
from repro.train.engine import (
    build_schedule,
    chunk_ranges,
    train_population_sharded,
)

from conftest import tiny_data_fn as _data_fn
from conftest import tiny_init as _init
from conftest import tiny_loss_fn as _loss_fn

KEY = jax.random.key(0)


def _run_pair(kind, optimizer="sgd", steps=13, population=4, record_every=5,
              **mix_kw):
    tcfg = TrainConfig(
        population=population, optimizer=optimizer,
        lr=0.05 if optimizer == "sgd" else 1e-3,
        total_steps=steps, batch_size=4,
    )
    mcfg = MixingConfig(kind=kind, mode="bucketed", **mix_kw)
    ref = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=record_every
    )
    engine_mod.reset_chunk_trace_count()
    fused = train_population_sharded(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=record_every
    )
    # the compile-count contract holds for EVERY pair the parity suite runs:
    # one trace per schedule variant, never more than two
    traces = engine_mod.chunk_trace_count()
    variants = build_schedule(steps, record_every, mcfg).variants()
    assert traces == len(variants) <= 2, (kind, steps, record_every, traces)
    return ref, fused


@pytest.mark.parametrize(
    "kind,kw",
    [
        ("wash", dict(base_p=0.5)),
        ("wash_opt", dict(base_p=0.5)),
        ("papa", dict(papa_every=5, papa_alpha=0.9)),
        ("papa_all", dict(papa_all_every=4)),
        ("none", dict()),
    ],
)
def test_engines_match_all_mixing_modes(kind, kw):
    ref, fused = _run_pair(kind, **kw)
    for a, b in zip(
        jax.tree_util.tree_leaves(ref.population),
        jax.tree_util.tree_leaves(fused.population),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ref.comm_scalars == fused.comm_scalars
    assert ref.history["step"] == fused.history["step"]
    np.testing.assert_allclose(
        ref.history["loss"], fused.history["loss"], rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        ref.history["comm"], fused.history["comm"], rtol=0, atol=0
    )
    np.testing.assert_allclose(
        ref.history["consensus"], fused.history["consensus"], rtol=1e-5, atol=1e-6
    )


def test_wash_opt_replays_plan_on_adamw_moments():
    """WASH+Opt inside the fused step must shuffle mu AND nu with the same
    plan as the reference (comm triples, moments match bitwise)."""
    ref, fused = _run_pair("wash_opt", optimizer="adamw", base_p=0.5)
    for mk in ("mu", "nu"):
        for a, b in zip(
            jax.tree_util.tree_leaves(ref.opt_state[mk]),
            jax.tree_util.tree_leaves(fused.opt_state[mk]),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    wash_ref, wash_fused = _run_pair("wash", optimizer="adamw", base_p=0.5)
    assert fused.comm_scalars == 3 * wash_fused.comm_scalars
    assert ref.comm_scalars == fused.comm_scalars


def test_engine_dispatch_via_train_population():
    """train_population(engine="shard_map") routes to the fused engine."""
    tcfg = TrainConfig(population=3, optimizer="sgd", lr=0.05, total_steps=6,
                       batch_size=4)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    ref = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3
    )
    fused = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3,
        engine="shard_map",
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref.population),
        jax.tree_util.tree_leaves(fused.population),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        train_population(
            KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, engine="nope"
        )


def test_shard_map_engine_rejects_dense_plans():
    """Dense-mode WASH has no collective lowering: the fused engine must
    refuse it loudly instead of silently training a different algorithm."""
    tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.05, total_steps=2,
                       batch_size=4)
    dense = MixingConfig(kind="wash", base_p=0.5, mode="dense")
    with pytest.raises(ValueError, match="bucketed"):
        train_population_sharded(
            KEY, _init, _loss_fn, _data_fn, tcfg, dense, 2
        )
    # non-WASH kinds don't read mode — dense config is fine there
    papa = MixingConfig(kind="papa", mode="dense", papa_every=2)
    train_population_sharded(
        KEY, _init, _loss_fn, _data_fn, tcfg, papa, 2, record_every=2
    )


def test_serving_consumes_either_engine():
    """averaged_params must produce the identical soup from both engines'
    results (TrainResult or bare population)."""
    ref, fused = _run_pair("wash", base_p=0.5, steps=6)
    soup_ref = averaged_params(ref)
    soup_fused = averaged_params(fused.population)
    for a, b in zip(
        jax.tree_util.tree_leaves(soup_ref),
        jax.tree_util.tree_leaves(soup_fused),
    ):
        assert a.shape == b.shape  # ens axis averaged away
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_ranges_cover_and_align():
    for total, every in [(1, 25), (13, 5), (60, 20), (7, 7), (100, 1)]:
        chunks = chunk_ranges(total, every)
        flat = [s for a, b in chunks for s in range(a, b)]
        assert flat == list(range(total))
        # every chunk ends on a reference-loop record boundary
        for _, stop in chunks:
            s = stop - 1
            assert s % every == 0 or s == total - 1


def test_explicit_mesh_roundtrips_through_train_population():
    """A caller-supplied 1-device ens mesh must reach the fused engine
    through the public API (PR 1 silently dropped it) and reproduce the
    default-mesh run bitwise; the vmap engine must reject a mesh loudly
    rather than ignore it."""
    tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05, total_steps=6,
                       batch_size=4)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    mesh = jax.make_mesh((1,), ("ens",), axis_types=(AxisType.Auto,))
    explicit = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3,
        engine="shard_map", mesh=mesh,
    )
    default = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3,
        engine="shard_map",
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(explicit.population),
        jax.tree_util.tree_leaves(default.population),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="mesh"):
        train_population(
            KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, mesh=mesh
        )
    with pytest.raises(ValueError, match="engine_opts"):
        train_population(
            KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2,
            engine_opts={"async_staging": False},
        )


def test_async_staging_matches_sync():
    """The double-buffered staging thread must not change data order,
    key derivation, or results — bitwise-equal to synchronous staging."""
    tcfg = TrainConfig(population=4, optimizer="sgd", lr=0.05, total_steps=9,
                       batch_size=4)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    kw = dict(record_every=4)
    a = train_population_sharded(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2,
        async_staging=True, **kw,
    )
    b = train_population_sharded(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2,
        async_staging=False, **kw,
    )
    for x, y in zip(
        jax.tree_util.tree_leaves(a.population),
        jax.tree_util.tree_leaves(b.population),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a.history["loss"] == b.history["loss"]
    assert a.comm_scalars == b.comm_scalars


def test_comm_accounting_exact_on_synthetic_past_2pow24_plan():
    """A synthetic bucketed plan selecting > 2^24 scalars per step: the
    host-side accounting both engines share must stay integer-exact where
    a float32-carried scalar (the pre-fix scan carry) truncates."""
    from repro.core import shuffle as shf
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.core.mixing import static_mix_comm

    n = 2
    sent = 2 ** 24 + 1          # odd -> not representable in float32
    d = n * sent
    # synthetic (n, k_per) bucketed plan: only its shape enters accounting
    plan = {"w": jax.ShapeDtypeStruct((n, sent), jnp.int32)}
    exact = float(shf.plan_sent_scalars(plan, n, mode="bucketed"))
    assert exact == sent
    assert float(jnp.float32(exact)) != exact  # the old carry truncated this

    # static_mix_comm reproduces the same count from shapes alone (no
    # device compute: eval_shape), for the config the slow e2e test runs
    member = {"w": jax.ShapeDtypeStruct((d,), jnp.float32)}
    lids = infer_layer_ids(member, 1)
    mcfg = MixingConfig(kind="wash", base_p=1.0, schedule="constant",
                        mode="bucketed")
    got = static_mix_comm(member, mcfg, lids, total_layers(1), n)
    assert got == exact


@pytest.mark.slow
def test_comm_parity_exact_past_2pow24_end_to_end():
    """Regression for the float32 comm carry: one real fused-vs-reference
    step whose plan sends 2^24+1 scalars per member.  Pre-fix, both
    engines reported 2^24 (the nearest f32); the host-side accounting must
    report the exact odd count, identically in both."""
    sent = 2 ** 24 + 1
    d = 2 * sent

    def init(k):
        return {"w": jax.random.normal(k, (d,), jnp.float32) * 0.01}

    def data_fn(m, step, k):
        return {"t": jnp.zeros((1, 1), jnp.float32)}

    def loss_fn(p, b):
        return jnp.mean(p["w"] ** 2) + 0.0 * jnp.sum(b["t"])

    tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.1, total_steps=1,
                       batch_size=1)
    mcfg = MixingConfig(kind="wash", base_p=1.0, schedule="constant",
                        mode="bucketed")
    ref = train_population(
        KEY, init, loss_fn, data_fn, tcfg, mcfg, 1, record_every=1
    )
    fused = train_population_sharded(
        KEY, init, loss_fn, data_fn, tcfg, mcfg, 1, record_every=1
    )
    assert ref.comm_scalars == fused.comm_scalars == float(sent)
    assert ref.history["comm"] == fused.history["comm"] == [float(sent)]


def test_engine_opts_pallas_shuffle_parity():
    """engine_opts["pallas_shuffle"]: the fused engine's shuffle applies
    through the fused Pallas kernel (chip-local exchanges, i.e. the
    1-device mesh here).  The kernel output itself is bitwise-equal to the
    roll path (tests/test_kernels.py asserts that), but swapping it into
    the donated fori_loop changes how XLA fuses the SURROUNDING optimizer
    arithmetic — the same ~1ulp fusion sensitivity the engine docs note
    for select-masking — so the end-to-end contract here is near-exact,
    with identical comm accounting and history schedule."""
    tcfg = TrainConfig(population=4, optimizer="adamw", lr=1e-3,
                       total_steps=6, batch_size=4)
    mcfg = MixingConfig(kind="wash_opt", base_p=0.5, mode="bucketed")
    ref = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3
    )
    fused = train_population(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3,
        engine="shard_map", engine_opts={"pallas_shuffle": True},
    )
    for a, b in zip(
        jax.tree_util.tree_leaves((ref.population, ref.opt_state["mu"])),
        jax.tree_util.tree_leaves((fused.population, fused.opt_state["mu"])),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )
    assert ref.comm_scalars == fused.comm_scalars
    assert ref.history["step"] == fused.history["step"]


def test_record_fn_runs_at_boundaries():
    tcfg = TrainConfig(population=2, optimizer="sgd", lr=0.05, total_steps=7,
                       batch_size=4)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    seen = []
    res = train_population_sharded(
        KEY, _init, _loss_fn, _data_fn, tcfg, mcfg, 2, record_every=3,
        record_fn=lambda step, pop_: seen.append(step) or {"probe": float(step)},
    )
    assert seen == [0, 3, 6] == res.history["step"]
    assert res.history["probe"] == [0.0, 3.0, 6.0]
