"""Fused shard_map training engine: one donated jit per schedule chunk.

The reference loop (:mod:`repro.train.loop`) dispatches two separate jits
per step (optimizer update, then mixing) from a Python loop, so the WASH
communication story is simulated on a stacked array rather than exercised.
This engine runs the whole train+mix step as ONE donated jit under
``shard_map`` over an ``ens`` mesh axis:

  * each mesh shard holds a contiguous block of n_local = N / mesh_ens
    members (one member per device on a TPU ensemble mesh; the whole
    population on the 1-device CPU fallback),
  * WASH shuffles travel over the real ``ppermute`` path
    (:func:`repro.core.shuffle.bucketed_apply_collective_blocked`) and
    PAPA pulls over ``pmean``, instead of the stacked gather,
  * ``lax.scan`` runs each chunk of the host-side dispatch plan
    (:mod:`repro.train.schedule`) in a single dispatch.  Chunks are padded
    to one fixed scan length per compiled variant and split along
    ``mixing_due`` gate runs, so the engine traces **at most two**
    executables per run (one collective, one collective-free) no matter
    how ``(total_steps, record_every)`` fall — and exactly one when the
    gates never change inside a record window (WASH, ``none``),
  * the mixing schedule (:func:`repro.core.mixing.mixing_due` per step) is
    threaded through the fused loop as a static-shaped gate vector, the
    per-step ``valid`` mask lowers to the loop's traced trip count (pad
    slots sit past it and never execute), and the WASH plan is built once
    per step from the shared key and replayed on the optimizer moments
    (WASH+Opt) inside the fused step,
  * batches for chunk k+1 are stacked and ``device_put`` on a staging
    thread while chunk k executes (double buffering), instead of PR 1's
    synchronous per-chunk host loop,
  * communication is accounted host-side in exact float64 from the static
    plan sizes (:func:`repro.core.mixing.static_mix_comm`) — a float32
    scalar carried through ``lax.scan`` truncates past 2^24 scalars per
    step, far below real model sizes.

WASH kinds always use the ``bucketed`` plan mode here (the dense mode has
no collective lowering); everything else — init, data order, key
derivation, optimizer arithmetic, comm accounting — matches the reference
loop exactly, which `tests/test_engine_parity.py` asserts.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import TrainConfig
from repro.core import population as pop
from repro.core import shardplan
from repro.core.consensus import avg_distance_to_consensus
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import (
    MixingConfig,
    mix_collective_blocked,
    static_mix_comm,
)
from repro.core.prng import step_key
from repro.sharding import rules as sharding_rules
from repro.optim import cosine_lr, make_optimizer
from repro.train.loop import TrainResult
from repro.train.schedule import (  # noqa: F401  (re-exported API)
    ChunkPlan,
    Schedule,
    build_schedule,
    chunk_ranges,
    num_pipeline_ticks,
    record_boundaries,
    split_microbatch_sizes,
)

PyTree = Any

# Counts traces of the fused chunk body (shard_map+jit trace the Python
# body exactly once per compiled executable, so this IS the compile count;
# asserted ≤ 2 per run by tests/test_schedule.py).
_CHUNK_TRACES = [0]


def reset_chunk_trace_count() -> None:
    _CHUNK_TRACES[0] = 0


def chunk_trace_count() -> int:
    return _CHUNK_TRACES[0]


# Below this average steps-per-chunk, CPU runs are FASTER synchronous:
# the per-chunk thread handoff outweighs the overlapped staging work
# (measured ~0.91x sync at 1-step chunks with heavy batch leaves; break
# even by ~3 steps).  On accelerators the host stages while the device
# computes, so the overlap always pays once there is a chunk to overlap.
ASYNC_STAGING_MIN_CHUNK_STEPS = 2


def resolve_async_staging(async_staging: Optional[bool],
                          chunks: List[ChunkPlan],
                          backend: Optional[str] = None) -> bool:
    """Tri-state gate for double-buffered staging.  Explicit True/False
    wins.  ``None`` auto-resolves: off with nothing to overlap (< 2
    chunks), off on CPU when the schedule's average chunk is shorter
    than :data:`ASYNC_STAGING_MIN_CHUNK_STEPS` real steps (the staging
    thread's handoff costs more than it hides there), on otherwise."""
    if async_staging is not None:
        return bool(async_staging)
    if len(chunks) < 2:
        return False
    if backend is None:
        backend = jax.default_backend()
    if backend == "cpu":
        avg = sum(c.length for c in chunks) / len(chunks)
        return avg >= ASYNC_STAGING_MIN_CHUNK_STEPS
    return True


def make_fused_chunk_fn(
    mesh,
    mcfg: MixingConfig,
    layer_ids: PyTree,
    tl: int,
    opt_update: Callable,
    loss_fn: Callable[[PyTree, Any], jax.Array],
    pspec: PyTree,
    ospec: PyTree,
    bspecs: PyTree,
    *,
    with_mixing: bool = True,
    donate: bool = True,
    pplan: Optional[shardplan.PopulationPlan] = None,
    use_pallas: bool = False,
):
    """Build the engine's fused chunk dispatch: one donated jit scanning
    (per-member update → gated collective mix) over a chunk of steps under
    shard_map.  ``with_mixing=False`` builds the collective-free variant
    dispatched on no-mix gate runs (the only other executable the engine
    ever compiles).  Exposed so benchmarks time the SHIPPED engine body
    rather than a copy (``benchmarks/kernels_bench.py``; pass
    ``donate=False`` there so repeated timing calls can reuse inputs).

    ``pplan`` (a :class:`repro.core.shardplan.PopulationPlan`) switches the
    body to the multi-axis mesh layout: the population is sharded over
    ``pplan.pop_axes``, members over ``pplan.dp_axes``-split batches with
    gradients ``pmean``-ed back, model-sharded leaves are all-gathered for
    the black-box ``loss_fn`` and re-sliced for the shard-local optimizer
    update, and mixing runs on shard-local plans
    (:func:`repro.core.shardplan.mix_collective_sharded`).  ``pplan=None``
    keeps the single-``ens``-axis body bit-for-bit unchanged."""
    pop_axes = pplan.pop_axes if pplan is not None else ("ens",)
    dp_axes = pplan.dp_axes if pplan is not None else ()
    # gather/slice only when something actually needs it, so the trivial
    # multi-axis case keeps the exact single-axis dataflow (bitwise parity)
    gathered = pplan is not None and (pplan.any_sharded or bool(dp_axes))
    loss_axes = "ens" if pplan is None else pop_axes + dp_axes

    def chunk_fn(population, opt_state, batches, lrs, keydata, gates, n_valid):
        _CHUNK_TRACES[0] += 1
        # host-side effect at trace time only: the compile counter mirrors
        # the ≤2-executables contract _CHUNK_TRACES guards
        obs.get().record_compile("train_chunk", mixing=bool(with_mixing))

        # the loss rides the fori_loop carry, whose dtype is fixed up
        # front — derive it from loss_fn so non-f32 losses (x64, bf16)
        # keep working like they did under lax.scan's unconstrained ys.
        # Member templates use the FULL member shapes (loss_fn sees
        # gathered leaves when the members are model-sharded); batch
        # templates stay local (loss_fn sees this chip's batch shard).
        if pplan is not None:
            member_sds = jax.tree_util.tree_unflatten(
                pplan.treedef,
                [jax.ShapeDtypeStruct(info.member_shape, x.dtype)
                 for info, x in zip(
                     pplan.infos, jax.tree_util.tree_flatten(population)[0]
                 )],
            )
        else:
            member_sds = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                population,
            )
        loss_sds = jax.eval_shape(
            loss_fn,
            member_sds,
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape[2:], x.dtype), batches
            ),
        )

        def body(i, carry):
            p, s, _ = carry
            batch, lr, kd, gate = jax.tree_util.tree_map(
                lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                (batches, lrs, keydata, gates),
            )

            if gathered:
                # FSDP-style step for model-sharded members: gather full
                # leaves for the black-box loss (an exact reconstruction),
                # pmean gradients over any batch-splitting data axes, then
                # slice this chip's shard back for the elementwise
                # optimizer update — bitwise equal to updating the same
                # shard of an unsharded member.
                p_full = shardplan.all_gather_population(p, pplan)
                losses, g_full = jax.vmap(
                    lambda pm, bm: jax.value_and_grad(loss_fn)(pm, bm)
                )(p_full, batch)
                if dp_axes:
                    g_full = jax.tree_util.tree_map(
                        lambda x: lax.pmean(x, dp_axes), g_full
                    )
                g_loc = shardplan.shard_population(g_full, pplan)
                p2, s2 = jax.vmap(
                    lambda pm, gm, sm: opt_update(pm, gm, sm, lr)
                )(p, g_loc, s)
            else:
                def one(pm, sm, bm):
                    loss, g = jax.value_and_grad(loss_fn)(pm, bm)
                    p2_, s2_ = opt_update(pm, g, sm, lr)
                    return p2_, s2_, loss

                p2, s2, losses = jax.vmap(one)(p, s, batch)

            if with_mixing:
                k = jax.random.wrap_key_data(kd)
                if pplan is not None:
                    p3, s3 = shardplan.mix_collective_sharded(
                        k, p2, s2, mcfg, pplan, gate, use_pallas=use_pallas
                    )
                else:
                    p3, s3 = mix_collective_blocked(
                        k, p2, s2, mcfg, layer_ids, tl, "ens", gate,
                        use_pallas=use_pallas,
                    )
            else:
                p3, s3 = p2, s2
            loss_mean = lax.pmean(jnp.mean(losses), loss_axes)
            if loss_mean.dtype != loss_sds.dtype or getattr(
                loss_mean.aval, "weak_type", False
            ):
                # normalize odd loss dtypes so the carry signature is
                # stable; trace-time check keeps the common path's graph
                # free of an extra convert
                loss_mean = loss_mean.astype(loss_sds.dtype)
            return (p3, s3, loss_mean)

        # A bounded fori_loop, not lax.scan: inputs are padded to the
        # variant's fixed length but pad slots NEVER execute — the traced
        # trip count stops the loop after the chunk's real steps.  This
        # keeps one compile per variant without select-masking the
        # optimizer update (a masking `where` changes XLA's fusion of the
        # update arithmetic by ~1ulp, breaking the bitwise-parity
        # contract) and spends zero FLOPs on pad slots.  lax.scan lowers
        # to the same while+dynamic-slice structure, so the executed
        # per-step dataflow is unchanged.
        p, s, loss_last = lax.fori_loop(
            0, n_valid, body,
            (population, opt_state, jnp.zeros((), loss_sds.dtype)),
        )
        return p, s, loss_last

    f = jax.shard_map(
        chunk_fn,
        mesh=mesh,
        in_specs=(pspec, ospec, bspecs, P(), P(), P(), P()),
        out_specs=(pspec, ospec, P()),
        check_vma=False,
    )
    return jax.jit(f, donate_argnums=(0, 1) if donate else ())


class StageFns(NamedTuple):
    """The three pieces of a member's loss for the pipelined engine.

    The engine never inspects where the blocks live — stage-splitting is
    done entirely by the PartitionSpecs
    (:func:`repro.sharding.rules.stage_member_specs`), so ``blocks``
    receives the full member params and reads its (stage-local, under
    ``shard_map``) stacked-blocks leaves itself.
    """

    embed: Callable[[PyTree, Any], jax.Array]          # (params, batch) -> x
    blocks: Callable[[PyTree, jax.Array], jax.Array]   # (params, x) -> x
    head: Callable[[PyTree, jax.Array, Any], jax.Array]  # -> scalar loss


def make_pipelined_chunk_fn(
    mesh,
    mcfg: MixingConfig,
    layer_ids: PyTree,
    tl: int,
    opt_update: Callable,
    stage_fns: StageFns,
    pspec: PyTree,
    ospec: PyTree,
    bspecs: PyTree,
    *,
    num_micro: int,
    pplan: shardplan.PopulationPlan,
    with_mixing: bool = True,
    donate: bool = True,
    use_pallas: bool = False,
):
    """Pipeline-parallel variant of :func:`make_fused_chunk_fn`.

    One donated jit scanning (microbatched pipelined update → gated
    collective mix) over a chunk of steps under ``shard_map`` on a mesh
    with a ``pipe`` axis.  Each step runs a GPipe-style schedule of
    ``num_micro + S - 1`` ticks inside a ``lax.scan``: at tick ``t``
    stage ``s`` runs microbatch ``t - s`` through its block slice and
    ships the boundary activation to stage ``s + 1`` with a single
    ``ppermute`` over ``pipe``; ticks outside a stage's live window
    compute masked junk that never reaches the loss.  Reverse-mode AD
    transposes the ``ppermute`` chain into the backward pipeline
    automatically, so one ``value_and_grad`` gives exact microbatch-
    accumulated gradients (mean of per-microbatch means — equal
    microbatch sizes are enforced by the driver).  Pipe-replicated
    leaves (embed/head/norms) get their gradients ``psum``-med over
    ``pipe`` (each stage contributes only its own, mostly-zero slice of
    the chain rule), which also keeps their replicas bitwise in sync.

    The ≤2-trace contract, the donated-buffer discipline, and the
    fori_loop trip-count padding are inherited unchanged.
    """
    S = int(mesh.shape["pipe"])
    num_ticks = num_pipeline_ticks(num_micro, S)
    pipe_perm = [(s_, s_ + 1) for s_ in range(S - 1)]
    dp_axes = pplan.dp_axes
    loss_axes = pplan.pop_axes + dp_axes
    flat_lids = jax.tree_util.tree_flatten(layer_ids)[0]

    def _sync_pipe_grads(g):
        """psum pipe-replicated (non-stage-split) leaves' grads over pipe."""
        flat, td = jax.tree_util.tree_flatten(g)
        out = [
            gl if not isinstance(lid, int) else lax.psum(gl, "pipe")
            for gl, lid in zip(flat, flat_lids)
        ]
        return jax.tree_util.tree_unflatten(td, out)

    def chunk_fn(population, opt_state, batches, lrs, keydata, gates, n_valid):
        _CHUNK_TRACES[0] += 1
        obs.get().record_compile(
            "train_chunk_pipelined", mixing=bool(with_mixing)
        )
        sid = lax.axis_index("pipe")

        def member_loss(pm, mb):
            # mb leaves are (num_micro, b, ...); losses accumulate in f32
            x_sds = jax.eval_shape(
                stage_fns.embed, pm,
                jax.tree_util.tree_map(lambda x: x[0], mb),
            )

            def tick(carry, t):
                recv, acc = carry
                m = t - sid
                mi = jnp.clip(m, 0, num_micro - 1)
                mbt = jax.tree_util.tree_map(
                    lambda x: lax.dynamic_index_in_dim(
                        x, mi, 0, keepdims=False
                    ),
                    mb,
                )
                x0 = stage_fns.embed(pm, mbt)
                y = stage_fns.blocks(pm, jnp.where(sid == 0, x0, recv))
                lv = stage_fns.head(pm, y, mbt)
                active = (m >= 0) & (m < num_micro) & (sid == S - 1)
                acc = acc + jnp.where(active, lv.astype(jnp.float32), 0.0)
                sent = lax.ppermute(y, "pipe", perm=pipe_perm)
                return (sent, acc), None

            (_, acc), _ = lax.scan(
                tick,
                (jnp.zeros(x_sds.shape, x_sds.dtype),
                 jnp.zeros((), jnp.float32)),
                jnp.arange(num_ticks, dtype=jnp.int32),
            )
            # nonzero only on the last stage; _sync_pipe_grads/psum below
            # restore the global view
            return acc / num_micro

        def body(i, carry):
            p, s, _ = carry
            batch, lr, kd, gate = jax.tree_util.tree_map(
                lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
                (batches, lrs, keydata, gates),
            )
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    (x.shape[0], num_micro, x.shape[1] // num_micro)
                    + x.shape[2:]
                ),
                batch,
            )
            losses, g = jax.vmap(
                lambda pm, bm: jax.value_and_grad(member_loss)(pm, bm)
            )(p, micro)
            g = _sync_pipe_grads(g)
            if dp_axes:
                g = jax.tree_util.tree_map(
                    lambda x: lax.pmean(x, dp_axes), g
                )
            p2, s2 = jax.vmap(
                lambda pm, gm, sm: opt_update(pm, gm, sm, lr)
            )(p, g, s)
            if with_mixing:
                k = jax.random.wrap_key_data(kd)
                p3, s3 = shardplan.mix_collective_sharded(
                    k, p2, s2, mcfg, pplan, gate, use_pallas=use_pallas
                )
            else:
                p3, s3 = p2, s2
            loss_mean = lax.pmean(
                jnp.mean(lax.psum(losses, "pipe")), loss_axes
            )
            return (p3, s3, loss_mean.astype(jnp.float32))

        p, s, loss_last = lax.fori_loop(
            0, n_valid, body,
            (population, opt_state, jnp.zeros((), jnp.float32)),
        )
        return p, s, loss_last

    f = jax.shard_map(
        chunk_fn,
        mesh=mesh,
        in_specs=(pspec, ospec, bspecs, P(), P(), P(), P()),
        out_specs=(pspec, ospec, P()),
        check_vma=False,
    )
    return jax.jit(f, donate_argnums=(0, 1) if donate else ())


def _state_shapes(init_fn, opt_init, key, n: int, same_init: bool):
    """The population + optimizer-state initializer and its output shapes.

    Shapes come from ``eval_shape``: the specs, layer ids and comm
    accounting are derived before anything is allocated, so the state is
    built once, directly in its mesh layout (:func:`_init_placed`)."""

    def init_state(k):
        population = pop.init_population(init_fn, k, n, same_init=same_init)
        return population, jax.vmap(opt_init)(population)

    population, opt_state = jax.eval_shape(init_state, key)
    return init_state, population, opt_state


def _init_placed(init_state, key, mesh, pspec, ospec):
    """Run ``init_state`` under a jit whose outputs land in the chunk
    program's own layout (``NamedSharding(mesh, spec)``): each device
    materializes only its members, and the first chunk sees the same
    input shardings as every later one, so it traces once, not twice."""
    def place(specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    return jax.jit(
        init_state, out_shardings=(place(pspec), place(ospec))
    )(key)


def train_population_sharded(
    key: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    loss_fn: Callable[[PyTree, Any], jax.Array],
    data_fn: Callable[[int, int, jax.Array], Any],
    tcfg: TrainConfig,
    mcfg: MixingConfig,
    num_blocks: int,
    record_every: int = 25,
    record_fn: Optional[Callable[[int, PyTree], Dict[str, float]]] = None,
    mesh=None,
    async_staging: Optional[bool] = None,
    split_gate_runs: bool = True,
    param_specs=None,
    pallas_shuffle: bool = False,
) -> TrainResult:
    """Drop-in replacement for :func:`repro.train.loop.train_population`
    running the fused shard_map engine.  Same signature plus an optional
    ``mesh`` (default: the host's ``ens``-only mesh; 2D/3D
    ``(ens[, data][, model])`` meshes route mixing through the shard-local
    planner — see :mod:`repro.core.shardplan` — and shard batches over the
    data axes), ``async_staging`` (double-buffer chunk k+1's batches on a
    staging thread while chunk k executes; ``None`` auto-gates via
    :func:`resolve_async_staging` — off on CPU schedules whose chunks are
    too short to amortize the thread handoff), ``split_gate_runs`` (dispatch
    no-mix spans on the collective-free executable; see
    :mod:`repro.train.schedule`), ``param_specs`` (member-level
    ``PartitionSpec``s, e.g. from :func:`repro.sharding.rules.param_pspecs`;
    requires a mesh with the named axes) and ``pallas_shuffle`` (apply
    bucketed shuffles through the fused Pallas kernel where the exchange
    is chip-local)."""
    if mcfg.kind in ("wash", "wash_opt") and mcfg.mode != "bucketed":
        raise ValueError(
            f"engine='shard_map' only lowers bucketed WASH plans; got "
            f"mode={mcfg.mode!r}.  Use mode='bucketed' (identical in "
            f"expectation, Eq. 4) or engine='vmap' for dense plans."
        )
    n = tcfg.population
    if mesh is None:
        from repro.launch.mesh import make_host_ensemble_mesh

        mesh = make_host_ensemble_mesh(n)
    multi = len(mesh.axis_names) > 1
    if param_specs is not None and not multi:
        raise ValueError(
            "param_specs shard members over mesh axes; pass a multi-axis "
            "mesh (e.g. repro.launch.mesh.make_host_mesh) along with them"
        )

    opt_init, opt_update = make_optimizer(
        tcfg.optimizer, momentum=tcfg.momentum, weight_decay=tcfg.weight_decay
    )
    init_state, population, opt_state = _state_shapes(
        init_fn, opt_init, key, n, tcfg.same_init
    )
    # exact per-mix-step comm from the static plan sizes (member template:
    # shapes only, no data copy); never None here — dense WASH was rejected
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population
    )
    lids = infer_layer_ids(member_tpl, num_blocks)
    tl = total_layers(num_blocks)

    use_pallas = pallas_shuffle or mcfg.pallas_shuffle
    if multi:
        member_specs = (
            param_specs if param_specs is not None
            else jax.tree_util.tree_map(lambda _: P(), member_tpl)
        )
        pplan = shardplan.plan_population_mixing(
            mesh, member_tpl, member_specs, mcfg, lids, tl, n
        )
        pspec = sharding_rules.population_pspecs(member_specs, pplan.pop_axes)
        ospec = sharding_rules.opt_pspecs(opt_state, pspec, pplan.pop_axes)
        comm_per_mix_step = shardplan.static_shard_mix_comm(
            pplan, opt_state=opt_state
        )
        pop_entry = (
            pplan.pop_axes[0] if len(pplan.pop_axes) == 1
            else tuple(pplan.pop_axes)
        )
        dp_sizes = 1
        for a in pplan.dp_axes:
            dp_sizes *= pplan.size(a)
    else:
        pplan = None
        m = int(mesh.shape["ens"])
        assert n % m == 0, f"population {n} must divide over ens axis of size {m}"
        pspec = jax.tree_util.tree_map(lambda _: P("ens"), population)
        ospec = jax.tree_util.tree_map(lambda _: P("ens"), opt_state)
        comm_per_mix_step = static_mix_comm(
            member_tpl, mcfg, lids, tl, n, opt_state=opt_state
        )
        pop_entry = "ens"
        dp_sizes = 1
    assert comm_per_mix_step is not None
    population, opt_state = _init_placed(init_state, key, mesh, pspec, ospec)

    # Leftover data axes split each member's batch only when EVERY batch
    # leaf's leading dim divides (all-or-nothing, so a split leaf never
    # pairs with a replicated one inside a shard); otherwise batches
    # replicate over dp and the gradient pmean is an exact identity.
    split_batch_over_dp = False
    if pplan is not None and pplan.dp_axes:
        try:
            probe = jax.eval_shape(
                lambda k: data_fn(0, 0, k), jax.random.fold_in(key, 0)
            )
        except Exception:  # non-traceable data_fn: probe with a real call
            probe = data_fn(0, 0, jax.random.fold_in(key, 0))
        split_batch_over_dp = all(
            leaf.shape and leaf.shape[0] % dp_sizes == 0
            for leaf in jax.tree_util.tree_leaves(probe)
        )

    def _batch_leaf_spec(shape) -> P:
        """(pad_len, n, B, ...) leaf: member axis over the population axes,
        the per-member batch over leftover data axes when they split."""
        if split_batch_over_dp:
            return P(None, pop_entry, tuple(pplan.dp_axes))
        return P(None, pop_entry)

    sched = build_schedule(
        tcfg.total_steps, record_every, mcfg, split_gate_runs=split_gate_runs
    )

    fused: Dict[bool, Callable] = {}  # variant (with_mixing) -> donated jit

    def get_fused(chunk: ChunkPlan, batches):
        if chunk.mixing not in fused:
            bspecs = jax.tree_util.tree_map(
                lambda x: _batch_leaf_spec(x.shape), batches
            )
            fused[chunk.mixing] = make_fused_chunk_fn(
                mesh, mcfg, lids, tl, opt_update, loss_fn,
                pspec, ospec, bspecs, with_mixing=chunk.mixing,
                pplan=pplan, use_pallas=use_pallas,
            )
        return fused[chunk.mixing]

    return _run_chunked_schedule(
        mesh=mesh, n=n, tcfg=tcfg, data_fn=data_fn, sched=sched,
        get_fused=get_fused, population=population, opt_state=opt_state,
        comm_per_mix_step=comm_per_mix_step, record_fn=record_fn,
        batch_leaf_spec=_batch_leaf_spec, key=key,
        async_staging=async_staging,
    )


def _run_chunked_schedule(
    *,
    mesh,
    n: int,
    tcfg: TrainConfig,
    data_fn: Callable,
    sched: Schedule,
    get_fused: Callable,
    population: PyTree,
    opt_state: PyTree,
    comm_per_mix_step: float,
    record_fn,
    batch_leaf_spec: Callable,
    key: jax.Array,
    async_staging: Optional[bool],
) -> TrainResult:
    """The engines' shared dispatch loop: stage each chunk's inputs
    (double-buffered on a staging thread when
    :func:`resolve_async_staging` allows), run its donated executable,
    accumulate exact host-side comm, and record history at the reference
    loop's boundaries.  Shared verbatim by the single-stage and pipelined
    engines — key derivation, padding, and staging are identical, so the
    pipelined engine inherits the bitwise data order."""
    base_key = jax.random.fold_in(key, 1234)
    data_key = jax.random.fold_in(key, 5678)
    rep_sharding = NamedSharding(mesh, P())

    def stage(chunk: ChunkPlan):
        """Stack a chunk's inputs, pad to the variant's fixed scan length
        (pad slots replicate the last real step; they sit past the fused
        loop's trip count and never execute), and start the device
        transfers.  Runs on the staging thread."""
        steps = list(chunk.steps)
        member_batches = []
        for step in steps:
            dk = jax.random.fold_in(data_key, step)
            member_batches += [
                data_fn(mm, step, jax.random.fold_in(dk, mm)) for mm in range(n)
            ]
        member_batches += member_batches[-n:] * chunk.pad
        # one stack per leaf for the whole (pad_len, n, ...) block — not a
        # stack per step then per chunk.  data_fn outputs live on device
        # (jax.random), so host-side np.stack would force a sync instead
        # of saving one; the single device stack keeps staging dispatches
        # at one per leaf.
        batches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs).reshape(
                (chunk.pad_len, n) + xs[0].shape
            ),
            *member_batches,
        )
        lr_list = [
            cosine_lr(s, tcfg.total_steps, tcfg.lr, tcfg.min_lr, tcfg.warmup_steps)
            for s in steps
        ]
        lrs = jnp.stack(lr_list + [lr_list[-1]] * chunk.pad)
        kd_list = [jax.random.key_data(step_key(base_key, s)) for s in steps]
        keydata = jnp.stack(kd_list + [kd_list[-1]] * chunk.pad)
        gates = jnp.asarray(chunk.padded_gates(), jnp.float32)
        # trip count of the fused fori_loop: pad slots past it never execute
        n_valid = jnp.asarray(chunk.length, jnp.int32)

        batches = jax.device_put(batches, jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, batch_leaf_spec(x.shape)), batches
        ))
        lrs, keydata, gates, n_valid = jax.device_put(
            (lrs, keydata, gates, n_valid), rep_sharding
        )
        return batches, lrs, keydata, gates, n_valid

    history: Dict[str, List[float]] = {
        "step": [], "loss": [], "consensus": [], "comm": []
    }
    comm_total = 0.0
    chunks = sched.chunks
    executor = (
        ThreadPoolExecutor(max_workers=1, thread_name_prefix="wash-stage")
        if resolve_async_staging(async_staging, chunks) and len(chunks) > 1
        else None
    )

    tel = obs.get()
    # mirrors comm_total add-for-add (same value, same order, from 0.0),
    # so the counter snapshot bit-equals the exact float64 accounting
    comm_counter = tel.registry.counter("train.comm_scalars") if tel.enabled else None

    def staged_timed(chunk: ChunkPlan):
        # runs on the staging thread when double-buffered: the histogram's
        # total vs wall time is the staging-thread occupancy
        with tel.span("train.stage", step=chunk.stop - 1):
            return stage(chunk)

    t0 = time.time()
    try:
        nxt = executor.submit(staged_timed, chunks[0]) if executor else None
        for i, chunk in enumerate(chunks):
            staged = nxt.result() if executor else staged_timed(chunk)
            if executor and i + 1 < len(chunks):
                # double buffering: the staging thread builds chunk i+1's
                # inputs while the devices execute chunk i
                nxt = executor.submit(staged_timed, chunks[i + 1])

            with tel.span("train.chunk_execute", step=chunk.stop - 1,
                          mixing=chunk.mixing):
                population, opt_state, loss_last = get_fused(
                    chunk, staged[0]
                )(population, opt_state, *staged)
            mix_steps = 0
            for g in chunk.gates:  # per-step float64 adds, as the reference
                if g:
                    comm_total += comm_per_mix_step
                    mix_steps += 1
                    if comm_counter is not None:
                        comm_counter.inc(comm_per_mix_step)
            if mix_steps and tel.enabled:
                tel.event("train.comm_volume",
                          comm_per_mix_step=comm_per_mix_step,
                          mix_steps=mix_steps, comm_total=comm_total)

            if chunk.record:
                step = chunk.stop - 1  # chunk boundary == record boundary
                history["step"].append(step)
                history["loss"].append(float(loss_last))
                history["consensus"].append(
                    float(avg_distance_to_consensus(population))
                )
                history["comm"].append(comm_total)
                extras = {}
                if record_fn is not None:
                    for k_, v in record_fn(step, population).items():
                        history.setdefault(k_, []).append(v)
                        extras[k_] = v
                if tel.enabled:
                    wall = time.time() - t0
                    if wall > 0:
                        tel.registry.gauge("train.steps_per_s").set(
                            chunk.stop / wall
                        )
                    tel.event("train.record", step=step,
                              loss=history["loss"][-1],
                              consensus=history["consensus"][-1],
                              comm=comm_total, **extras)
    finally:
        if executor is not None:
            executor.shutdown(wait=True)

    history["wall_s"] = [time.time() - t0]
    if tel.enabled:
        tel.registry.gauge("train.wall_s").set(history["wall_s"][0])
    return TrainResult(population, opt_state, history, comm_total)


def train_population_pipelined(
    key: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    stage_fns,
    data_fn: Callable[[int, int, jax.Array], Any],
    tcfg: TrainConfig,
    mcfg: MixingConfig,
    num_blocks: int,
    record_every: int = 25,
    record_fn: Optional[Callable[[int, PyTree], Dict[str, float]]] = None,
    mesh=None,
    microbatches: int = 1,
    async_staging: Optional[bool] = None,
    split_gate_runs: bool = True,
    param_specs=None,
    pallas_shuffle: bool = False,
) -> TrainResult:
    """Pipeline-parallel counterpart of :func:`train_population_sharded`.

    Takes :class:`StageFns` ``(embed, blocks, head)`` instead of a
    monolithic ``loss_fn`` so the engine can cut the forward pass at the
    stage boundaries; ``mesh`` must carry a ``pipe`` axis
    (``launch.mesh`` kinds ``ens_pp``/``ens_dp_pp``).  Each member's
    stacked-blocks leaves are sharded over ``pipe``
    (:func:`repro.sharding.rules.stage_member_specs`) into contiguous
    stages; every optimizer step splits its batch into ``microbatches``
    equal microbatches and runs the GPipe schedule of
    :func:`make_pipelined_chunk_fn`.  WASH mixing runs on per-stage
    plans whose ppermute rings stay inside each stage's ens slice
    (:mod:`repro.core.shardplan`).

    Parity contract (asserted by ``tests/test_pipeline.py``): with one
    stage and one microbatch this delegates to the fused single-stage
    engine and is bitwise-identical to it; with ``S > 1`` the result
    matches to numerical tolerance (microbatch gradient accumulation is
    a mean of per-microbatch means, which reorders float sums).
    """
    if microbatches < 1:
        raise ValueError(f"microbatches must be >= 1, got {microbatches}")
    n = tcfg.population
    if mesh is None:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(n, "ens_pp")
    if "pipe" not in mesh.axis_names:
        raise ValueError(
            f"the pipelined engine needs a mesh with a 'pipe' axis "
            f"(launch.mesh kinds ens_pp/ens_dp_pp); got {mesh.axis_names}"
        )
    sf = StageFns(*stage_fns)
    S = int(mesh.shape["pipe"])

    if S == 1 and microbatches == 1:
        # the degenerate pipeline IS the single-stage engine: compose the
        # loss and delegate, so (E, 1, 1, S=1) meshes are bitwise-identical
        # to the existing fused path (size-1 axes drop out of the
        # classification and the specs)
        def loss_fn(pm, b):
            return sf.head(pm, sf.blocks(pm, sf.embed(pm, b)), b)

        return train_population_sharded(
            key, init_fn, loss_fn, data_fn, tcfg, mcfg, num_blocks,
            record_every=record_every, record_fn=record_fn, mesh=mesh,
            async_staging=async_staging, split_gate_runs=split_gate_runs,
            param_specs=param_specs, pallas_shuffle=pallas_shuffle,
        )

    if mcfg.kind in ("wash", "wash_opt") and mcfg.mode != "bucketed":
        raise ValueError(
            f"engine='shard_map' only lowers bucketed WASH plans; got "
            f"mode={mcfg.mode!r}."
        )

    opt_init, opt_update = make_optimizer(
        tcfg.optimizer, momentum=tcfg.momentum, weight_decay=tcfg.weight_decay
    )
    init_state, population, opt_state = _state_shapes(
        init_fn, opt_init, key, n, tcfg.same_init
    )
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population
    )
    lids = infer_layer_ids(member_tpl, num_blocks)
    tl = total_layers(num_blocks)

    flat_lids = jax.tree_util.tree_flatten(lids)[0]
    if not any(not isinstance(l, int) for l in flat_lids):
        raise ValueError(
            "stage-split training needs stacked-blocks leaves (one leaf "
            "spanning all blocks along axis 0); this member has only "
            "per-block leaves, which cannot be sharded over the pipe axis"
        )
    for lid, leaf in zip(flat_lids, jax.tree_util.tree_leaves(member_tpl)):
        if not isinstance(lid, int) and leaf.shape[0] % S:
            raise ValueError(
                f"stacked-blocks leaf of {leaf.shape[0]} layers does not "
                f"split evenly over {S} pipeline stages"
            )

    member_specs = (
        param_specs if param_specs is not None
        else jax.tree_util.tree_map(lambda _: P(), member_tpl)
    )
    stage_specs = sharding_rules.stage_member_specs(member_specs, lids, "pipe")
    pplan = shardplan.plan_population_mixing(
        mesh, member_tpl, stage_specs, mcfg, lids, tl, n
    )
    pspec = sharding_rules.population_pspecs(stage_specs, pplan.pop_axes)
    ospec = sharding_rules.opt_pspecs(opt_state, pspec, pplan.pop_axes)
    comm_per_mix_step = shardplan.static_shard_mix_comm(
        pplan, opt_state=opt_state
    )
    population, opt_state = _init_placed(init_state, key, mesh, pspec, ospec)
    pop_entry = (
        pplan.pop_axes[0] if len(pplan.pop_axes) == 1
        else tuple(pplan.pop_axes)
    )
    dp_sizes = 1
    for a in pplan.dp_axes:
        dp_sizes *= pplan.size(a)

    try:
        probe = jax.eval_shape(
            lambda k: data_fn(0, 0, k), jax.random.fold_in(key, 0)
        )
    except Exception:  # non-traceable data_fn: probe with a real call
        probe = data_fn(0, 0, jax.random.fold_in(key, 0))
    split_batch_over_dp = bool(pplan.dp_axes) and all(
        leaf.shape and leaf.shape[0] % dp_sizes == 0
        for leaf in jax.tree_util.tree_leaves(probe)
    )
    for leaf in jax.tree_util.tree_leaves(probe):
        local_b = leaf.shape[0] // (dp_sizes if split_batch_over_dp else 1)
        split_microbatch_sizes(local_b, microbatches)

    def _batch_leaf_spec(shape) -> P:
        if split_batch_over_dp:
            return P(None, pop_entry, tuple(pplan.dp_axes))
        return P(None, pop_entry)

    sched = build_schedule(
        tcfg.total_steps, record_every, mcfg, split_gate_runs=split_gate_runs
    )
    use_pallas = pallas_shuffle or mcfg.pallas_shuffle

    fused: Dict[bool, Callable] = {}

    def get_fused(chunk: ChunkPlan, batches):
        if chunk.mixing not in fused:
            bspecs = jax.tree_util.tree_map(
                lambda x: _batch_leaf_spec(x.shape), batches
            )
            fused[chunk.mixing] = make_pipelined_chunk_fn(
                mesh, mcfg, lids, tl, opt_update, sf,
                pspec, ospec, bspecs, num_micro=microbatches,
                with_mixing=chunk.mixing, pplan=pplan,
                use_pallas=use_pallas,
            )
        return fused[chunk.mixing]

    return _run_chunked_schedule(
        mesh=mesh, n=n, tcfg=tcfg, data_fn=data_fn, sched=sched,
        get_fused=get_fused, population=population, opt_state=opt_state,
        comm_per_mix_step=comm_per_mix_step, record_fn=record_fn,
        batch_leaf_spec=_batch_leaf_spec, key=key,
        async_staging=async_staging,
    )
