"""The shipped contract matrix: one :class:`~repro.analysis.contracts.Contract`
per compiled program the repo actually runs.

Five programs, five entries:

``train_chunk``
    The fused single-axis train chunk (``train.engine.make_fused_chunk_fn``
    on an ``("ens",)`` mesh): WASH mixing must lower collective-permutes
    plus the loss-``pmean`` all-reduce and nothing else, the
    ``donate_argnums=(0, 1)`` population/opt-state donation must survive
    to ``input_output_alias``, collectives move f32 only, the engine
    compiles at most two executables per run (mix / no-mix gate
    variants), and the host-side comm accounting is exact builtin-float64
    that replays bit-for-bit.

``pipelined_train``
    The pipelined chunk (``make_pipelined_chunk_fn`` on an (ens, pipe)
    mesh): same clauses, plus every collective-permute pair must be a
    stage-ring mixer hop (``src ≡ tgt mod S``), a one-stage-forward
    activation hop (``tgt == src + 1``), or the backward pass's
    AD-transposed gradient hop (``tgt == src - 1``).

``scan_decode``
    The serving engine's scan-decode body (``serving.engine``): a
    single-device program — no collectives at all — whose KV cache
    (argument 2) is donated and aliased, compiled once per prompt shape.

``continuous_decode``
    The continuous-batching decode step (``serving.batching``): no
    collectives, both paged KV pools (arguments 1 and 2) donated and
    aliased, compiled once per pool geometry across an entire mixed
    request stream — and reused by a second server on the same geometry.

``speculative_decode``
    The speculative decode step (``serving.speculative`` via
    ``serving.batching._build_spec_decode``): no collectives, all FOUR
    paged KV pools — verify k/v (arguments 2 and 3) AND draft k/v
    (arguments 4 and 5) — donated and aliased, compiled once per
    (geometry, ``draft_k``); a server with a different ``draft_k`` adds
    exactly one trace.

Each ``check_*`` raises :class:`~repro.analysis.contracts.ContractViolation`
on the first broken clause; :func:`run_matrix` runs every entry and
aggregates.  The matrix needs a forced multi-device CPU host
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — jax locks the
device count at first init, so ``tools/run_analysis.py`` sets the flag
before importing jax, and tests run it in a subprocess.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, PartitionSpec as P

from repro.analysis import contracts
from repro.analysis.contracts import (
    Contract, ContractViolation, backward_hop, check_compile_count,
    check_host_comm_f64, forward_hop, replay_comm, stage_ring,
)
from repro.configs.base import ModelConfig, TrainConfig
from repro.core import population as pop
from repro.core import shardplan
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import MixingConfig, mixing_due, static_mix_comm
from repro.optim import make_optimizer
from repro.sharding import rules as sharding_rules

ENTRIES = ("train_chunk", "pipelined_train", "scan_decode",
           "continuous_decode", "speculative_decode")

# (ens=2, pipe=2) plus the 8-device CI lane test_pipeline already forces
REQUIRED_DEVICES = 4

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _require_devices() -> None:
    if jax.device_count() < REQUIRED_DEVICES:
        raise RuntimeError(
            f"the contract matrix needs >= {REQUIRED_DEVICES} devices "
            f"(got {jax.device_count()}); run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 set BEFORE jax "
            f"first initializes (tools/run_analysis.py does this)")


# ---------------------------------------------------------------------------
# shared toy model (mirrors tests/test_pipeline.py's _TOY: stacked-blocks
# leaves so the same member splits over a pipe axis)
# ---------------------------------------------------------------------------

_L, _DIN, _D, _DOUT, _B, _N = 4, 16, 8, 4, 8, 2


def _toy_init(k):
    ks = jax.random.split(k, 3)
    return {"embed": {"w": jax.random.normal(ks[0], (_DIN, _D)) * 0.3},
            "blocks": {"w1": jax.random.normal(ks[1], (_L, _D, _D)) * 0.3},
            "head": {"w": jax.random.normal(ks[2], (_D, _DOUT)) * 0.3}}


def _toy_embed(p, b):
    return b["x"] @ p["embed"]["w"]


def _toy_blocks(p, x):
    def body(h, wl):
        return jnp.tanh(h @ wl) + h, None

    h, _ = lax.scan(body, x, p["blocks"]["w1"])
    return h


def _toy_head(p, x, b):
    return jnp.mean((x @ p["head"]["w"] - b["y"]) ** 2)


def _toy_loss(p, b):
    return _toy_head(p, _toy_blocks(p, _toy_embed(p, b)), b)


def _toy_data(m, step, k):
    kx, ky = jax.random.split(k)
    return {"x": jax.random.normal(kx, (_B, _DIN)),
            "y": jax.random.normal(ky, (_B, _DOUT))}


def _toy_tcfg(total_steps: int = 6) -> TrainConfig:
    return TrainConfig(population=_N, optimizer="sgd", lr=0.05,
                       total_steps=total_steps, batch_size=_B, seq_len=1,
                       seed=0)


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _chunk_args_sds(population, opt_state, pad_len: int = 3):
    """SDS templates in the fused/pipelined chunk signature
    ``(population, opt_state, batches, lrs, keydata, gates, n_valid)``
    — batch leaves carry the engine's (pad_len, n, B, ...) layout."""
    batches = {
        "x": jax.ShapeDtypeStruct((pad_len, _N, _B, _DIN), jnp.float32),
        "y": jax.ShapeDtypeStruct((pad_len, _N, _B, _DOUT), jnp.float32),
    }
    kd = jax.random.key_data(jax.random.key(0))
    return (
        _sds(population), _sds(opt_state), batches,
        jax.ShapeDtypeStruct((pad_len,), jnp.float32),
        jax.ShapeDtypeStruct((pad_len,) + kd.shape, kd.dtype),
        jax.ShapeDtypeStruct((pad_len,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )


def _tiny_model_cfg() -> ModelConfig:
    return ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=4,
                       num_heads=4, num_kv_heads=2, vocab_size=64,
                       max_position=128)


# ---------------------------------------------------------------------------
# entry 1: fused train chunk
# ---------------------------------------------------------------------------


def check_train_chunk() -> Dict[str, Any]:
    """Fused single-axis train chunk: permutes + loss all-reduce only, f32
    on the wire, population/opt-state donation aliased, <= 2 compiles per
    run, host comm accounting exact f64 and bit-replayable."""
    from repro.train import engine as T

    _require_devices()
    mesh = jax.make_mesh((_N,), ("ens",), axis_types=(AxisType.Auto,))
    key = jax.random.key(0)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    tcfg = _toy_tcfg()

    population = pop.init_population(_toy_init, key, _N,
                                     same_init=tcfg.same_init)
    lids = infer_layer_ids(pop.member(population, 0), _L)
    tl = total_layers(_L)
    opt_init, opt_update = make_optimizer(
        tcfg.optimizer, momentum=tcfg.momentum,
        weight_decay=tcfg.weight_decay)
    opt_state = jax.vmap(opt_init)(population)

    pspec = jax.tree_util.tree_map(lambda _: P("ens"), population)
    ospec = jax.tree_util.tree_map(lambda _: P("ens"), opt_state)
    bspecs = {"x": P(None, "ens"), "y": P(None, "ens")}
    chunk = T.make_fused_chunk_fn(mesh, mcfg, lids, tl, opt_update,
                                  _toy_loss, pspec, ospec, bspecs)

    contract = Contract(
        name="train_chunk",
        require_collectives=("collective-permute", "all-reduce"),
        forbid_collectives=("all-gather", "reduce-scatter", "all-to-all"),
        donate_argnums=(0, 1),
        collective_dtypes={k: ("f32",) for k in _COLLECTIVES},
    )
    report = contracts.lower_and_check(
        chunk, _chunk_args_sds(population, opt_state), contract)

    # compile count over a real (tiny) run: one executable per gate
    # variant, never re-traced per chunk
    T.reset_chunk_trace_count()
    result = T.train_population_sharded(
        key, _toy_init, _toy_loss, _toy_data, tcfg, mcfg, _L,
        record_every=3, mesh=mesh)
    check_compile_count("train_chunk-compiles", T.chunk_trace_count(), (1, 2))

    # host-side comm accounting: exact builtin f64, replayed bit-for-bit
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population)
    cps = static_mix_comm(member_tpl, mcfg, lids, tl, _N,
                          opt_state=opt_state)
    gates = [mixing_due(s, mcfg) for s in range(tcfg.total_steps)]
    replay = replay_comm(cps, gates)
    check_host_comm_f64(
        {"comm_per_mix_step": cps, "comm_scalars": result.comm_scalars,
         "replay": replay}, name="train_chunk-host-comm")
    if replay != result.comm_scalars:
        raise ContractViolation("train_chunk-host-comm", [
            f"replayed comm {replay!r} != engine comm "
            f"{result.comm_scalars!r} (accumulation order or per-step "
            f"value drifted)"])
    return {"hlo": report, "compiles": T.chunk_trace_count(),
            "comm_scalars": result.comm_scalars}


# ---------------------------------------------------------------------------
# entry 2: pipelined train chunk
# ---------------------------------------------------------------------------


def check_pipelined_train() -> Dict[str, Any]:
    """Pipelined chunk on an (ens=2, pipe=2) mesh: every permute is a
    stage-ring mixer hop, a one-stage-forward activation hop, or its
    AD-transposed backward gradient hop; donation and compile-count
    clauses as the fused chunk; shard-plan comm exact f64."""
    from repro.train import StageFns, train_population_pipelined
    from repro.train import engine as T

    _require_devices()
    S = 2
    mesh = jax.make_mesh((_N, S), ("ens", "pipe"),
                         axis_types=(AxisType.Auto,) * 2)
    key = jax.random.key(0)
    mcfg = MixingConfig(kind="wash", base_p=0.5, mode="bucketed")
    tcfg = _toy_tcfg()
    sf = StageFns(_toy_embed, _toy_blocks, _toy_head)

    population = pop.init_population(_toy_init, key, _N,
                                     same_init=tcfg.same_init)
    lids = infer_layer_ids(pop.member(population, 0), _L)
    tl = total_layers(_L)
    opt_init, opt_update = make_optimizer(
        tcfg.optimizer, momentum=tcfg.momentum,
        weight_decay=tcfg.weight_decay)
    opt_state = jax.vmap(opt_init)(population)

    # mirror train_population_pipelined's spec/plan construction exactly
    member_tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), population)
    member_specs = jax.tree_util.tree_map(lambda _: P(), member_tpl)
    stage_specs = sharding_rules.stage_member_specs(member_specs, lids,
                                                    "pipe")
    pplan = shardplan.plan_population_mixing(
        mesh, member_tpl, stage_specs, mcfg, lids, tl, _N)
    pspec = sharding_rules.population_pspecs(stage_specs, pplan.pop_axes)
    ospec = sharding_rules.opt_pspecs(opt_state, pspec, pplan.pop_axes)
    pop_entry = (pplan.pop_axes[0] if len(pplan.pop_axes) == 1
                 else tuple(pplan.pop_axes))
    bspecs = {"x": P(None, pop_entry), "y": P(None, pop_entry)}
    chunk = T.make_pipelined_chunk_fn(
        mesh, mcfg, lids, tl, opt_update, sf, pspec, ospec, bspecs,
        num_micro=2, pplan=pplan)

    contract = Contract(
        name="pipelined_train",
        require_collectives=("collective-permute", "all-reduce"),
        forbid_collectives=("all-gather", "reduce-scatter", "all-to-all"),
        permute_rules=(stage_ring(S), forward_hop(S), backward_hop(S)),
        donate_argnums=(0, 1),
        collective_dtypes={k: ("f32",) for k in _COLLECTIVES},
    )
    report = contracts.lower_and_check(
        chunk, _chunk_args_sds(population, opt_state), contract)

    T.reset_chunk_trace_count()
    result = train_population_pipelined(
        key, _toy_init, sf, _toy_data, tcfg, mcfg, _L, record_every=3,
        mesh=mesh, microbatches=2)
    check_compile_count("pipelined_train-compiles", T.chunk_trace_count(),
                        (1, 2))

    cps = shardplan.static_shard_mix_comm(pplan, opt_state=opt_state)
    gates = [mixing_due(s, mcfg) for s in range(tcfg.total_steps)]
    replay = replay_comm(cps, gates)
    check_host_comm_f64(
        {"comm_per_mix_step": cps, "comm_scalars": result.comm_scalars,
         "replay": replay}, name="pipelined_train-host-comm")
    if replay != result.comm_scalars:
        raise ContractViolation("pipelined_train-host-comm", [
            f"replayed comm {replay!r} != engine comm "
            f"{result.comm_scalars!r}"])
    return {"hlo": report, "compiles": T.chunk_trace_count(),
            "comm_scalars": result.comm_scalars}


# ---------------------------------------------------------------------------
# entry 3: scan decode (serving engine)
# ---------------------------------------------------------------------------


def check_scan_decode() -> Dict[str, Any]:
    """Serving scan decode: a collective-free single-device program whose
    KV cache (arg 2) is donated and aliased, compiled once per prompt
    shape (counter stays at 1 across repeat same-shape requests, +1 for a
    new shape)."""
    from repro.models import transformer as M
    from repro.serving import engine as E

    cfg = _tiny_model_cfg()
    B, S, max_new = 2, 4, 8
    capacity = E.internal_prefix(cfg) + S + max_new

    params_sds = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache_sds = jax.eval_shape(lambda: M.init_cache(cfg, B, capacity))
    key_dtype = jax.eval_shape(lambda: jax.random.key(0)).dtype
    args = (
        params_sds,
        jax.ShapeDtypeStruct((B, S), jnp.int32),
        cache_sds,
        jax.ShapeDtypeStruct((B, 1, cfg.vocab_size), jnp.float32),
        jax.ShapeDtypeStruct((B,), key_dtype),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    # probe the raw program body with explicit donation: the serving path
    # routes donation through compat.donate_argnums, a no-op on CPU, so
    # the alias contract must be asserted on the body itself
    program = E._decode_program(cfg, False, S, max_new, True)
    contract = Contract(
        name="scan_decode",
        forbid_collectives=_COLLECTIVES,
        donate_argnums=(2,),
    )
    report = contracts.lower_and_check(program, args, contract)

    # one executable per prompt shape
    E.reset_trace_counts()
    E.clear_executable_cache()
    params = M.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
    E.generate(params, cfg, batch, max_new)
    E.generate(params, cfg, batch, max_new)
    check_compile_count("scan_decode-compiles-per-shape",
                        E.decode_trace_count(), 1)
    E.generate(params, cfg, {"tokens": jnp.zeros((B, S + 1), jnp.int32)},
               max_new)
    check_compile_count("scan_decode-compiles-new-shape",
                        E.decode_trace_count(), 2)
    return {"hlo": report, "compiles": E.decode_trace_count()}


# ---------------------------------------------------------------------------
# entry 4: continuous decode step (paged serving)
# ---------------------------------------------------------------------------


def check_continuous_decode() -> Dict[str, Any]:
    """Continuous-batching decode step: collective-free, both paged KV
    pools (args 1 and 2) donated and aliased, compiled once per pool
    geometry across a whole mixed stream — and reused by a second server
    on the same geometry."""
    from repro.models import layers as L
    from repro.models import transformer as M
    from repro.serving import batching

    cfg = ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, vocab_size=50,
                      max_position=128)
    page_size, max_slots, num_pages = 4, 3, 32

    params_sds = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    pools_sds = jax.eval_shape(
        lambda: L.paged_pools_init(cfg, num_pages, page_size,
                                   cfg.num_layers))
    key_dtype = jax.eval_shape(lambda: jax.random.key(0)).dtype
    B = max_slots
    args = (
        params_sds, pools_sds["k"], pools_sds["v"],
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_),
        jax.ShapeDtypeStruct((B, num_pages), jnp.int32),
        jax.ShapeDtypeStruct((B,), key_dtype),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    program = batching._build_decode(cfg, False, True, False)
    contract = Contract(
        name="continuous_decode",
        forbid_collectives=_COLLECTIVES,
        donate_argnums=(1, 2),
    )
    report = contracts.lower_and_check(program, args, contract)

    # one executable per pool geometry for a whole mixed stream, reused
    # by a second server on the same geometry
    batching.reset_trace_counts()
    batching.clear_executable_cache()
    params = M.init_params(jax.random.key(0), cfg)
    reqs = [batching.Request(uid=i, tokens=list(range(1, 1 + s)), max_new=m)
            for i, (s, m) in enumerate([(5, 6), (9, 3), (3, 8), (7, 5)])]
    server = batching.ContinuousServer(
        params, cfg, temperature=0.0, page_size=page_size,
        max_slots=max_slots, num_pages=num_pages)
    server.run(reqs)
    check_compile_count("continuous_decode-compiles-per-geometry",
                        batching.decode_trace_count(), 1)
    server2 = batching.ContinuousServer(
        params, cfg, temperature=0.0, page_size=page_size,
        max_slots=max_slots, num_pages=num_pages)
    server2.run([batching.Request(uid=90, tokens=[1, 2, 3], max_new=4)])
    check_compile_count("continuous_decode-compiles-reuse",
                        batching.decode_trace_count(), 1)
    return {"hlo": report, "compiles": batching.decode_trace_count()}


# ---------------------------------------------------------------------------
# entry 5: speculative decode step (draft + batched verify)
# ---------------------------------------------------------------------------


def check_speculative_decode() -> Dict[str, Any]:
    """Speculative decode step: collective-free, donation honored on all
    four paged KV pools (verify args 2–3, draft args 4–5), one executable
    per (pool geometry, ``draft_k``) across a whole speculative stream —
    and a server with a different ``draft_k`` adds exactly one trace."""
    from repro.models import layers as L
    from repro.models import transformer as M
    from repro.serving import batching

    cfg = ModelConfig(name="tiny", d_model=32, d_ff=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, vocab_size=50,
                      max_position=128)
    page_size, max_slots, num_pages, draft_k = 4, 3, 32, 3

    params_sds = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    pools_sds = jax.eval_shape(
        lambda: L.paged_pools_init(cfg, num_pages, page_size,
                                   cfg.num_layers))
    key_dtype = jax.eval_shape(lambda: jax.random.key(0)).dtype
    B = max_slots
    args = (
        params_sds, params_sds,                        # verify + draft (soup)
        pools_sds["k"], pools_sds["v"],                # verify pools
        pools_sds["k"], pools_sds["v"],                # draft pools
        jax.ShapeDtypeStruct((B,), jnp.int32),         # tokens
        jax.ShapeDtypeStruct((B,), jnp.int32),         # positions
        jax.ShapeDtypeStruct((B,), jnp.int32),         # steps
        jax.ShapeDtypeStruct((B,), jnp.int32),         # budgets
        jax.ShapeDtypeStruct((B,), jnp.bool_),         # active
        jax.ShapeDtypeStruct((B, num_pages), jnp.int32),
        jax.ShapeDtypeStruct((B,), key_dtype),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    program = batching._build_spec_decode(cfg, False, True, False, draft_k)
    contract = Contract(
        name="speculative_decode",
        forbid_collectives=_COLLECTIVES,
        donate_argnums=(2, 3, 4, 5),
    )
    report = contracts.lower_and_check(program, args, contract)

    # one executable per (geometry, draft_k) for a whole speculative
    # stream; a different draft_k is a new program — exactly one more
    batching.reset_trace_counts()
    batching.clear_executable_cache()
    params = M.init_params(jax.random.key(0), cfg)
    reqs = [batching.Request(uid=i, tokens=list(range(1, 1 + s)), max_new=m)
            for i, (s, m) in enumerate([(5, 6), (9, 3), (3, 8), (7, 5)])]
    server = batching.ContinuousServer(
        params, cfg, temperature=0.0, page_size=page_size,
        max_slots=max_slots, num_pages=num_pages,
        speculative=True, draft_k=draft_k)
    server.run(reqs)
    check_compile_count("speculative_decode-compiles-per-geometry",
                        batching.decode_trace_count(), 1)
    server2 = batching.ContinuousServer(
        params, cfg, temperature=0.0, page_size=page_size,
        max_slots=max_slots, num_pages=num_pages,
        speculative=True, draft_k=draft_k)
    server2.run([batching.Request(uid=90, tokens=[1, 2, 3], max_new=4)])
    check_compile_count("speculative_decode-compiles-reuse",
                        batching.decode_trace_count(), 1)
    server3 = batching.ContinuousServer(
        params, cfg, temperature=0.0, page_size=page_size,
        max_slots=max_slots, num_pages=num_pages,
        speculative=True, draft_k=draft_k + 2)
    server3.run([batching.Request(uid=91, tokens=[1, 2, 3], max_new=4)])
    check_compile_count("speculative_decode-compiles-new-draft-k",
                        batching.decode_trace_count(), 2)
    return {"hlo": report, "compiles": batching.decode_trace_count()}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

_CHECKS: Dict[str, Callable[[], Dict[str, Any]]] = {
    "train_chunk": check_train_chunk,
    "pipelined_train": check_pipelined_train,
    "scan_decode": check_scan_decode,
    "continuous_decode": check_continuous_decode,
    "speculative_decode": check_speculative_decode,
}


def run_matrix(entries: Optional[Tuple[str, ...]] = None,
               raise_on_violation: bool = True) -> Dict[str, Any]:
    """Run the contract matrix.  Returns ``{entry: result_dict}``; on any
    :class:`ContractViolation` raises one aggregate violation naming every
    failed entry (or records ``{"error": ...}`` per entry when
    ``raise_on_violation=False``)."""
    names = entries or ENTRIES
    unknown = set(names) - set(_CHECKS)
    if unknown:
        raise ValueError(f"unknown matrix entries {sorted(unknown)}; "
                         f"known: {list(ENTRIES)}")
    results: Dict[str, Any] = {}
    failures: List[str] = []
    for name in names:
        try:
            results[name] = _CHECKS[name]()
        except ContractViolation as e:
            results[name] = {"error": str(e)}
            failures.append(f"{name}: {e}")
    if failures and raise_on_violation:
        raise ContractViolation("matrix", failures)
    return results
