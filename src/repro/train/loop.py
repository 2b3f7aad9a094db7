"""Population training loop (paper Alg. 1).

Alternates per step:  (1) an independent optimizer step per member on its
own data stream (vmapped over the stacked ens axis), then (2) the
configured mixing op (WASH shuffle / PAPA EMA / PAPA-all average / none).

The loop works for any model: the caller supplies ``loss_fn(params, batch)``
and ``data_fn(member, step, key) -> batch``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import TrainConfig
from repro.core import population as pop
from repro.core.compat import donate_argnums
from repro.core.consensus import avg_distance_to_consensus
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import MixingConfig, mix_once, mixing_due, static_mix_comm
from repro.core.prng import step_key
from repro.optim import cosine_lr, make_optimizer

PyTree = Any


@dataclasses.dataclass
class TrainResult:
    population: PyTree
    opt_state: PyTree
    history: Dict[str, List[float]]
    comm_scalars: float  # total scalars sent per member over training


def train_population(
    key: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    loss_fn: Callable[[PyTree, Any], jax.Array],
    data_fn: Callable[[int, int, jax.Array], Any],
    tcfg: TrainConfig,
    mcfg: MixingConfig,
    num_blocks: int,
    record_every: int = 25,
    record_fn: Optional[Callable[[int, PyTree], Dict[str, float]]] = None,
    engine: str = "vmap",
    mesh=None,
    engine_opts: Optional[Dict[str, Any]] = None,
) -> TrainResult:
    """Train a population.  ``engine="vmap"`` is this module's two-jit
    reference loop; ``engine="shard_map"`` dispatches to the fused
    single-jit collective engine (:mod:`repro.train.engine`), which also
    receives ``mesh`` (an ``ens``-only or ``(ens[, data][, model])``
    mesh) and any ``engine_opts`` (``async_staging``/``split_gate_runs``/
    ``param_specs``/``pallas_shuffle``)."""
    if engine == "shard_map":
        from repro.train.engine import train_population_sharded

        return train_population_sharded(
            key, init_fn, loss_fn, data_fn, tcfg, mcfg, num_blocks,
            record_every=record_every, record_fn=record_fn, mesh=mesh,
            **(engine_opts or {}),
        )
    if engine != "vmap":
        raise ValueError(f"unknown engine {engine!r}")
    if mesh is not None:
        raise ValueError(
            "mesh= is only consumed by engine='shard_map'; the vmap "
            "reference loop runs on the default device"
        )
    if engine_opts:
        raise ValueError(
            f"engine_opts={sorted(engine_opts)} are only consumed by "
            "engine='shard_map'"
        )
    n = tcfg.population
    population = pop.init_population(init_fn, key, n, same_init=tcfg.same_init)
    lids = infer_layer_ids(pop.member(population, 0), num_blocks)
    tl = total_layers(num_blocks)

    opt_init, opt_update = make_optimizer(
        tcfg.optimizer, momentum=tcfg.momentum, weight_decay=tcfg.weight_decay
    )
    opt_state = jax.vmap(opt_init)(population)

    # donated on the chip, where the un-donated step would hold two copies
    # of the population and its optimizer state at once
    @functools.partial(jax.jit, donate_argnums=donate_argnums((0, 1)))
    def train_step(population, opt_state, batches, lr):
        def one(p, s, b):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            p2, s2 = opt_update(p, g, s, lr)
            return p2, s2, loss

        p2, s2, losses = jax.vmap(one, in_axes=(0, 0, 0))(population, opt_state, batches)
        return p2, s2, jnp.mean(losses)

    @functools.partial(jax.jit, donate_argnums=donate_argnums((0, 1)))
    def mix_step(population, opt_state, k):
        return mix_once(k, population, opt_state, mcfg, lids, tl)

    # exact float64 comm per mixing step from the static plan sizes; None
    # for dense WASH (data-dependent Bernoulli masks → use the device value)
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population
    )
    static_comm = static_mix_comm(
        member_tpl, mcfg, lids, tl, n, opt_state=opt_state
    )

    history: Dict[str, List[float]] = {
        "step": [], "loss": [], "consensus": [], "comm": []
    }
    comm_total = 0.0
    base_key = jax.random.fold_in(key, 1234)
    data_key = jax.random.fold_in(key, 5678)

    tel = obs.get()
    # mirrors comm_total add-for-add so the counter bit-equals the exact
    # host-side accounting (see the fused engine's identical mirror)
    comm_counter = tel.registry.counter("train.comm_scalars") if tel.enabled else None

    t0 = time.time()
    for step in range(tcfg.total_steps):
        lr = cosine_lr(step, tcfg.total_steps, tcfg.lr, tcfg.min_lr, tcfg.warmup_steps)
        dk = jax.random.fold_in(data_key, step)
        batches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[data_fn(m, step, jax.random.fold_in(dk, m)) for m in range(n)],
        )
        with tel.span("train.step", step=step):
            population, opt_state, loss = train_step(
                population, opt_state, batches, lr
            )

        if mixing_due(step, mcfg):
            population, opt_state, comm = mix_step(
                population, opt_state, step_key(base_key, step)
            )
            comm_step = float(comm) if static_comm is None else static_comm
            comm_total += comm_step
            if comm_counter is not None:
                comm_counter.inc(comm_step)
                tel.event("train.comm_volume", comm_per_mix_step=comm_step,
                          mix_steps=1, comm_total=comm_total)

        if step % record_every == 0 or step == tcfg.total_steps - 1:
            history["step"].append(step)
            history["loss"].append(float(loss))
            history["consensus"].append(float(avg_distance_to_consensus(population)))
            history["comm"].append(comm_total)
            extras = {}
            if record_fn is not None:
                for k_, v in record_fn(step, population).items():
                    history.setdefault(k_, []).append(v)
                    extras[k_] = v
            if tel.enabled:
                tel.registry.gauge("train.loss").set(history["loss"][-1])
                wall = time.time() - t0
                if wall > 0:
                    tel.registry.gauge("train.steps_per_s").set(
                        (step + 1) / wall
                    )
                # record_fn outputs become metric samples alongside the event
                for k_, v in extras.items():
                    tel.registry.gauge(f"train.record.{k_}").set(v)
                tel.event("train.record", step=step,
                          loss=history["loss"][-1],
                          consensus=history["consensus"][-1],
                          comm=comm_total, **extras)

    history["wall_s"] = [time.time() - t0]
    if tel.enabled:
        tel.registry.gauge("train.wall_s").set(history["wall_s"][0])
    return TrainResult(population, opt_state, history, comm_total)
