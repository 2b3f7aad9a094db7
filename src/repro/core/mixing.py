"""Population mixing strategies.

After every optimizer step the training loop calls ``mix`` on the stacked
population (or, in the distributed path, each member calls the collective
variant under ``shard_map``).  Implemented strategies:

  none      independent training (paper's Baseline)
  wash      parameter shuffling (paper Alg. 1)
  wash_opt  WASH + the same shuffle replayed on the optimizer moments
  papa      EMA pull toward consensus every T steps (PAPA, Eq. 1)
  papa_all  hard averaging every T_all steps (PAPA-all == DART)

Communication volume (scalars sent per member per mixing step) feeds the
paper's Table 1.  The stacked entry points report it per call; the fused
collective path (:func:`mix_collective_blocked`) does not — bucketed plan
sizes are static, so both training engines account communication
host-side in exact float64 via :func:`static_mix_comm` instead of
carrying a float32 scalar through the jitted step (which truncates past
2^24 scalars).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import shuffle as shf
from repro.core.schedules import active_window

PyTree = Any


@dataclasses.dataclass(frozen=True)
class MixingConfig:
    kind: str = "wash"           # none | wash | wash_opt | papa | papa_all
    base_p: float = 0.001        # WASH base probability (first layer)
    schedule: str = "decreasing" # decreasing | constant | increasing (Eq. 6 / Tab. 4)
    mode: str = "dense"          # dense | bucketed (see core.shuffle)
    papa_alpha: float = 0.99     # PAPA EMA coefficient (Eq. 1)
    papa_every: int = 10         # PAPA all-reduce period T
    papa_all_every: int = 1000   # PAPA-all / DART averaging period
    start_step: int = 0          # Fig. 5b ablation window
    stop_step: Optional[int] = None
    pallas_shuffle: bool = False # bucketed applies via the fused Pallas kernel

    def shuffles_optimizer(self) -> bool:
        return self.kind == "wash_opt"


def momentum_like_leaves(opt_state: PyTree, params: PyTree) -> PyTree:
    """The slice of the optimizer state that WASH+Opt shuffles.

    Our optimizers (repro.optim) store moments in a dict with the same
    sub-structure as params under keys 'mu' (SGD/Adam first moment) and
    optionally 'nu'.  Anything else (step counters) is left alone.
    """
    return {k: opt_state[k] for k in ("mu", "nu") if k in opt_state}


def _wash_step_stacked(
    key, params, opt_state, cfg: MixingConfig, layer_ids, total_layers
) -> Tuple[PyTree, PyTree, jax.Array]:
    n = jax.tree_util.tree_leaves(params)[0].shape[0]
    with jax.named_scope("wash"):
        with jax.named_scope("plan"):
            plan = shf.make_plan(
                key, params, layer_ids, total_layers, cfg.base_p,
                cfg.schedule, cfg.mode,
            )
        with jax.named_scope("shuffle"):
            new_params = shf.apply_plan_stacked(
                plan, params, cfg.mode, use_pallas=cfg.pallas_shuffle
            )
            new_opt = opt_state
            comm = shf.plan_sent_scalars(plan, n, cfg.mode)
            if cfg.shuffles_optimizer() and opt_state is not None:
                moments = momentum_like_leaves(opt_state, params)
                new_opt = dict(opt_state)
                for mk, mv in moments.items():
                    new_opt[mk] = shf.apply_plan_stacked(
                        plan, mv, cfg.mode, use_pallas=cfg.pallas_shuffle
                    )
                    comm = comm + shf.plan_sent_scalars(plan, n, cfg.mode)
    return new_params, new_opt, comm


def _papa_pull_stacked(params: PyTree, alpha: float) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: alpha * x + (1.0 - alpha) * jnp.mean(x, axis=0, keepdims=True),
        params,
    )


def _average_stacked(params: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape),
        params,
    )


def mixing_due(step: int, cfg: MixingConfig) -> bool:
    """Python-side period/window test so jitted mixing is unconditional."""
    if cfg.kind == "none" or not active_window(step, cfg.start_step, cfg.stop_step):
        return False
    if cfg.kind in ("wash", "wash_opt"):
        return True
    if cfg.kind == "papa":
        return step > 0 and step % cfg.papa_every == 0
    if cfg.kind == "papa_all":
        return step > 0 and step % cfg.papa_all_every == 0
    raise ValueError(f"unknown mixing kind {cfg.kind!r}")


def mix_once(
    key: jax.Array,
    params: PyTree,
    opt_state: Optional[PyTree],
    cfg: MixingConfig,
    layer_ids: PyTree,
    total_layers: int,
) -> Tuple[PyTree, Optional[PyTree], jax.Array]:
    """Unconditionally apply the strategy's op (period logic lives in
    :func:`mixing_due`).  Safe to jit with cfg/layer_ids static."""
    zero = jnp.zeros((), jnp.float32)
    n = jax.tree_util.tree_leaves(params)[0].shape[0]
    d = sum(x.size // n for x in jax.tree_util.tree_leaves(params))
    if cfg.kind in ("wash", "wash_opt"):
        return _wash_step_stacked(key, params, opt_state, cfg, layer_ids, total_layers)
    if cfg.kind == "papa":
        return _papa_pull_stacked(params, cfg.papa_alpha), opt_state, zero + float(d)
    if cfg.kind == "papa_all":
        return _average_stacked(params), opt_state, zero + float(d)
    return params, opt_state, zero


def mix_stacked(
    step: int,
    key: jax.Array,
    params: PyTree,
    opt_state: Optional[PyTree],
    cfg: MixingConfig,
    layer_ids: PyTree,
    total_layers: int,
) -> Tuple[PyTree, Optional[PyTree], jax.Array]:
    """Apply the configured mixing strategy to a stacked population.

    ``step`` must be a Python int (the period/window tests are static so
    no-mix steps trace to a no-op instead of a masked collective).
    Delegates the period/window test to :func:`mixing_due` and the op to
    :func:`mix_once` so the three mixing entry points cannot drift.
    Returns (params, opt_state, scalars_sent_per_member).
    """
    if not mixing_due(step, cfg):
        return params, opt_state, jnp.zeros((), jnp.float32)
    return mix_once(key, params, opt_state, cfg, layer_ids, total_layers)


def static_mix_comm(
    member_params: PyTree,
    cfg: MixingConfig,
    layer_ids: PyTree,
    total_layers: int,
    n: int,
    opt_state: Optional[PyTree] = None,
) -> Optional[float]:
    """Exact scalars sent per member on a mixing-due step, computed
    host-side in float64.

    Bucketed plan sizes are a pure function of shapes/N/p (the key only
    picks *which* coordinates move), so the count never has to ride a
    float32 device computation — which truncates past 2^24 scalars, well
    below real model sizes.  Both training engines use this value for
    their ``comm`` accounting, accumulating per-step on the host.

    ``member_params`` may be arrays or ``jax.ShapeDtypeStruct`` templates
    (only shapes are read).  Returns ``None`` when the count is
    data-dependent (dense WASH draws Bernoulli masks on device); callers
    then fall back to the device-reported value.
    """
    import numpy as np

    if cfg.kind == "none":
        return 0.0
    if cfg.kind in ("papa", "papa_all"):
        d = sum(
            int(np.prod(l.shape, dtype=np.int64))
            for l in jax.tree_util.tree_leaves(member_params)
        )
        return float(d)
    if cfg.mode != "bucketed":
        return None
    plan_shapes = jax.eval_shape(lambda: shf.make_plan(
        jax.random.key(0), member_params, layer_ids, total_layers,
        cfg.base_p, cfg.schedule, mode="bucketed", n=n,
    ))
    sel = sum(
        int(np.prod(p.shape, dtype=np.int64))
        for p in jax.tree_util.tree_leaves(
            plan_shapes, is_leaf=lambda x: x is None
        )
        if p is not None
    )
    comm = sel * (n - 1) / n
    if cfg.shuffles_optimizer() and opt_state is not None:
        comm = comm * (1 + len(momentum_like_leaves(opt_state, member_params)))
    return comm


# ---------------------------------------------------------------------------
# collective variants (one member per shard_map instance, ens as mesh axis)
# ---------------------------------------------------------------------------


def mix_collective(
    step: int,
    key: jax.Array,
    params: PyTree,
    opt_state: Optional[PyTree],
    cfg: MixingConfig,
    layer_ids: PyTree,
    total_layers: int,
    axis_name: str,
) -> Tuple[PyTree, Optional[PyTree], jax.Array]:
    """Distributed mixing: called per member under shard_map(axis_name=ens).

    WASH uses the bucketed plan (built from the *shared* key, so every
    member computes identical indices) and ``ppermute`` exchanges; PAPA
    uses ``pmean`` (all-reduce).
    """
    zero = jnp.zeros((), jnp.float32)
    if cfg.kind == "none" or not active_window(step, cfg.start_step, cfg.stop_step):
        return params, opt_state, zero

    n = lax.axis_size(axis_name)
    d = sum(x.size for x in jax.tree_util.tree_leaves(params))

    if cfg.kind in ("wash", "wash_opt"):
        with jax.named_scope("wash"):
            with jax.named_scope("plan"):
                plan = shf.make_plan(
                    key, params, layer_ids, total_layers, cfg.base_p,
                    cfg.schedule, mode="bucketed", n=n,
                )
            with jax.named_scope("shuffle"):
                new_params = shf.apply_plan_collective(plan, params,
                                                       axis_name)
                new_opt = opt_state
                comm = shf.plan_sent_scalars(plan, n, mode="bucketed")
                if cfg.shuffles_optimizer() and opt_state is not None:
                    new_opt = dict(opt_state)
                    for mk, mv in momentum_like_leaves(opt_state,
                                                       params).items():
                        new_opt[mk] = shf.apply_plan_collective(
                            plan, mv, axis_name)
                        comm = comm + shf.plan_sent_scalars(
                            plan, n, mode="bucketed")
            return new_params, new_opt, zero + comm

    if cfg.kind == "papa" and step % cfg.papa_every == 0 and step > 0:
        pulled = jax.tree_util.tree_map(
            lambda x: cfg.papa_alpha * x
            + (1.0 - cfg.papa_alpha) * lax.pmean(x, axis_name),
            params,
        )
        return pulled, opt_state, zero + float(d)

    if cfg.kind == "papa_all" and step % cfg.papa_all_every == 0 and step > 0:
        avg = jax.tree_util.tree_map(lambda x: lax.pmean(x, axis_name), params)
        return avg, opt_state, zero + float(d)

    return params, opt_state, zero


def mix_collective_blocked(
    key: jax.Array,
    params: PyTree,
    opt_state: Optional[PyTree],
    cfg: MixingConfig,
    layer_ids: PyTree,
    total_layers: int,
    axis_name: str,
    gate: jax.Array,
    use_pallas: bool = False,
) -> Tuple[PyTree, Optional[PyTree]]:
    """Fused-engine mixing on a *block* of members under shard_map.

    ``params`` leaves carry a leading local-ens axis (n_local members per
    shard; global population n = n_local * axis_size, so the same code
    serves one-member-per-device TPU meshes and the 1-device CPU fallback).

    ``gate`` is a traced {0,1} scalar — the Python-side :func:`mixing_due`
    result for this step, threaded through ``lax.scan`` — so the collective
    always executes with static shapes and the result is masked.  The WASH
    plan is built once from the shared key and replayed on the optimizer
    moments (WASH+Opt), exactly as in the stacked reference.

    Communication is NOT accounted here: plan sizes are static, so the
    host computes the exact float64 count via :func:`static_mix_comm`
    instead of carrying a float32 scalar through ``lax.scan`` (which
    silently truncates past 2^24 scalars per step).
    """
    if cfg.kind == "none":
        return params, opt_state

    n_local = jax.tree_util.tree_leaves(params)[0].shape[0]
    n = n_local * lax.axis_size(axis_name)

    def _gated(new_tree, old_tree):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(gate > 0, a, b), new_tree, old_tree
        )

    if cfg.kind in ("wash", "wash_opt"):
        with jax.named_scope("wash"):
            member = jax.tree_util.tree_map(lambda x: x[0], params)
            with jax.named_scope("plan"):
                plan = shf.make_plan(
                    key, member, layer_ids, total_layers, cfg.base_p,
                    cfg.schedule, mode="bucketed", n=n,
                )
            with jax.named_scope("shuffle"):
                new_params = shf.apply_plan_collective_blocked(
                    plan, params, axis_name, use_pallas=use_pallas
                )
                new_opt = opt_state
                if cfg.shuffles_optimizer() and opt_state is not None:
                    new_opt = dict(opt_state)
                    for mk, mv in momentum_like_leaves(opt_state,
                                                       params).items():
                        new_opt[mk] = _gated(
                            shf.apply_plan_collective_blocked(
                                plan, mv, axis_name, use_pallas=use_pallas
                            ),
                            mv,
                        )
            return _gated(new_params, params), new_opt

    if cfg.kind == "papa":
        pulled = jax.tree_util.tree_map(
            lambda x: cfg.papa_alpha * x
            + (1.0 - cfg.papa_alpha)
            * lax.pmean(jnp.mean(x, axis=0, keepdims=True), axis_name),
            params,
        )
        return _gated(pulled, params), opt_state

    if cfg.kind == "papa_all":
        avg = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                lax.pmean(jnp.mean(x, axis=0, keepdims=True), axis_name), x.shape
            ),
            params,
        )
        return _gated(avg, params), opt_state

    raise ValueError(f"unknown mixing kind {cfg.kind!r}")
