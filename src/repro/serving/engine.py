"""Fused scan-based serving engine: one compiled decode program per shape.

The legacy path (kept as :func:`generate_reference` for parity tests and
benchmarks) built a fresh ``jax.jit`` closure inside every ``generate``
call and drove it from a Python token loop — every *request* re-traced
``decode_step`` from scratch and every *token* paid a host dispatch plus a
list concat.  The engine here compiles the whole generation once:

  * prefill and decode are jitted top-level programs cached in a
    module-level **executable cache** keyed on
    ``(cfg, mode, B, S, max_new, capacity, greedy, mesh, stages)`` — one
    trace per shape for the lifetime of the process, reused across
    requests;
  * decode runs as a single ``lax.scan`` over token positions
    (:func:`repro.models.transformer.decode_scan`) with ``pos`` traced and
    the ``(B, S+max_new)`` token buffer preallocated and filled in-program;
  * the KV cache is **donated** to the decode program on backends whose
    runtime supports buffer donation (TPU/GPU; on CPU donation is a no-op
    and jax warns, so it is skipped there);
  * sampling happens in-scan: greedy, or temperature sampling with
    **per-request keys** (``jax.random.split(key, B)`` then a per-step
    ``fold_in``), so two requests in one batch never share a sample stream;
  * trace counters (:func:`decode_trace_count` — same pattern as
    ``train.engine.chunk_trace_count``) let tests assert that a 64-token
    generation compiles decode exactly once.

Serving **modes** (the paper's end-of-training evaluation strategies, made
first-class at serve time):

  soup      uniform weight average of the population — single-model cost,
            today's default (paper "Averaged").
  member    serve member *i* unaveraged (baseline / A-B debugging).
  ensemble  run all N members' prefill+decode under ``vmap`` and average
            their logits (``averaging.balanced_mean``) before sampling —
            the paper's accuracy ceiling at N× compute.

Batch sharding: pass a ``mesh`` with a ``data`` axis (e.g.
``launch.mesh.make_host_data_mesh``) and the token batch is sharded over
the data axes while params replicate — serving scales past one chip
without touching the program.

**Stage-split decode**: pass a ``("pipe",)`` mesh (``--pp-stages`` on the
serve CLI) and ``params["blocks"]`` plus the layer-leading KV cache are
sliced over ``pipe`` into ``S`` contiguous stages.  Each decode step runs
``S`` hops: every stage applies its local ``L/S`` blocks, the activation
crosses the stage boundary via ``ppermute``, and the last stage's logits
are ``psum``-broadcast so all stages sample the same token — staged
output is bitwise-identical to the unstaged engine (pure data movement).
Per-chip FLOPs match the replicated engine (``S`` hops x ``L/S`` layers);
the win is *memory* — each chip holds ``1/S`` of the blocks and cache, so
a model (or capacity) that does not fit one chip serves on ``S``.
:func:`repro.models.transformer.staged_decode_supported` gates the path
to the plain attention families (GQA/MLA); ensemble mode is rejected.

Handles the position bookkeeping for multimodal prefixes (VLM patches are
part of the internal sequence, so decode positions are offset by
``num_patches``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import averaging
from repro.core.compat import donate_argnums
from repro.core import population as pop
from repro.models import transformer as M

PyTree = Any

MODES = ("soup", "member", "ensemble")


def internal_prefix(cfg: ModelConfig) -> int:
    return cfg.num_patches if cfg.frontend == "vision" else 0


# ---------------------------------------------------------------------------
# trace counters + executable cache
# ---------------------------------------------------------------------------

# Counts traces of the fused decode/prefill program bodies (jit traces the
# Python body exactly once per compiled executable, so these ARE the
# compile counts; tests/test_serving.py asserts decode == 1 for a whole
# generation and stays 1 across same-shape requests).
_DECODE_TRACES = [0]
_PREFILL_TRACES = [0]
# Traces of the legacy reference loop's per-request jit closure.
_REFERENCE_TRACES = [0]

_EXEC_CACHE: Dict[Tuple, Callable] = {}


def reset_trace_counts() -> None:
    _DECODE_TRACES[0] = 0
    _PREFILL_TRACES[0] = 0
    _REFERENCE_TRACES[0] = 0


def decode_trace_count() -> int:
    return _DECODE_TRACES[0]


def prefill_trace_count() -> int:
    return _PREFILL_TRACES[0]


def reference_trace_count() -> int:
    return _REFERENCE_TRACES[0]


def executable_cache_size() -> int:
    return len(_EXEC_CACHE)


def clear_executable_cache() -> None:
    """Drop cached executables (tests use this to measure traces from cold)."""
    _EXEC_CACHE.clear()


# donation argnums, or () on CPU where donation is an ignored no-op
_donate = donate_argnums


# ---------------------------------------------------------------------------
# sampling (shared by the scan program and the reference loop)
# ---------------------------------------------------------------------------


def _request_keys(key: Optional[jax.Array], batch: int,
                  temperature: float) -> jax.Array:
    """Per-request sample keys.  Greedy decoding is keyless; temperature
    sampling REQUIRES an explicit key — a silent default key would make
    every temperature>0 request stream identical."""
    if temperature > 0.0:
        if key is None:
            raise ValueError(
                "generate(temperature>0) requires an explicit PRNG key: a "
                "default key would make all sampled requests identical. "
                "Pass key=jax.random.key(...) (greedy decoding stays keyless)."
            )
        return jax.random.split(key, batch)
    # unused by the greedy program; keeps one program signature per shape
    return jax.random.split(jax.random.key(0), batch)


def _sample(logits, keys, step, temperature, greedy: bool):
    """Next-token ids (B,) from last-position logits (B,1,V).

    ``step`` is folded into each request's key, so the stream at step t is
    independent of max_new_tokens and of the other requests in the batch.
    """
    last = logits[:, -1]
    if greedy:
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    ks = jax.vmap(lambda k: jax.random.fold_in(k, step))(keys)
    return jax.vmap(
        lambda lg, k: jax.random.categorical(k, lg)
    )(last.astype(jnp.float32) / temperature, ks).astype(jnp.int32)


# ---------------------------------------------------------------------------
# compiled programs
# ---------------------------------------------------------------------------


def _ensemble_step(cfg: ModelConfig):
    """Population decode step: vmap members, average logits before sampling
    (balanced-tree mean — same reduction as the weight soup)."""

    def step(params, cache, tokens, pos):
        lgs, cache = jax.vmap(
            lambda p, c: M.decode_step(p, cfg, tokens, c, pos)
        )(params, cache)
        return averaging.balanced_mean(lgs), cache

    return step


def _build_prefill(cfg: ModelConfig, ensemble: bool, capacity: int):
    def program(params, batch):
        _PREFILL_TRACES[0] += 1
        # trace-time host effect mirroring the one-trace-per-shape contract
        obs.get().record_compile("serve_prefill", capacity=capacity)
        if ensemble:
            return jax.vmap(
                lambda p: M.prefill(p, cfg, batch, capacity=capacity)
            )(params)
        return M.prefill(params, cfg, batch, capacity=capacity)

    return jax.jit(program)


def _decode_program(cfg: ModelConfig, ensemble: bool, S: int, max_new: int,
                    greedy: bool):
    """The raw (unjitted) scan-decode program body.

    Split from :func:`_build_decode` so the contract matrix
    (``repro.analysis.matrix``) can jit it with *explicit* donation and
    verify the KV-cache alias from optimized HLO even on CPU, where the
    serving path's :func:`repro.core.compat.donate_argnums` is a no-op.
    The cache is argument 2 — the donation contract's subject: the
    program returns ``(tokens, final_cache)`` so XLA can alias the
    donated input cache to the output (a donated buffer with no matching
    output is silently unusable — the contract matrix caught exactly
    that); :func:`generate` drops the cache half."""
    prefix = internal_prefix(cfg)

    def program(params, tokens, cache, first_logits, keys, temperature):
        _DECODE_TRACES[0] += 1
        obs.get().record_compile("serve_decode", S=S, max_new=max_new)
        B = tokens.shape[0]
        if ensemble:
            first_logits = averaging.balanced_mean(first_logits)
        nxt = _sample(first_logits, keys, 0, temperature, greedy)

        # preallocated (B, S+max_new) output buffer: prompt + every sampled
        # token is written in-program, no per-token host round-trip.
        buf = jnp.zeros((B, S + max_new), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, tokens.astype(jnp.int32), (0, 0))
        buf = buf.at[:, S].set(nxt)

        new_toks, cache = M.decode_scan(
            params, cfg, nxt, cache, prefix + S, max_new - 1,
            lambda lg, i: _sample(lg, keys, i + 1, temperature, greedy),
            step_fn=_ensemble_step(cfg) if ensemble else None,
        )
        return jax.lax.dynamic_update_slice(buf, new_toks, (0, S + 1)), cache

    return program


def _build_decode(cfg: ModelConfig, ensemble: bool, S: int, max_new: int,
                  greedy: bool):
    program = _decode_program(cfg, ensemble, S, max_new, greedy)
    return jax.jit(program, donate_argnums=_donate((2,)))


# ---------------------------------------------------------------------------
# stage-split programs (pipeline serving over a ("pipe",) mesh)
# ---------------------------------------------------------------------------


def _staged_param_specs(params) -> PyTree:
    """Member-param specs for the pipe mesh: stacked ``blocks`` leaves are
    stage-sliced on the scanned layer axis, everything else replicates."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [
        P("pipe") if any(getattr(p, "key", None) == "blocks" for p in path)
        else P()
        for path, _ in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def _staged_cache_specs(cfg: ModelConfig, stages: int, B: int, capacity: int):
    """(local_cfg, cache pspecs): every cache leaf leads with the layer
    axis, so the per-stage cache is the global one sharded by ``pipe``."""
    local_cfg = dataclasses.replace(cfg, num_layers=cfg.num_layers // stages)
    shapes = jax.eval_shape(lambda: M.init_cache(local_cfg, B, capacity))
    return local_cfg, jax.tree_util.tree_map(lambda _: P("pipe"), shapes)


def _staged_step_fn(cfg: ModelConfig, local_cfg: ModelConfig, stages: int):
    """decode_step over the pipe axis: ``S`` hops of local blocks + a
    boundary ``ppermute``; the last stage's logits are psum-broadcast so
    every stage samples the identical token (the psum adds exact zeros, so
    staged tokens are bitwise the unstaged engine's)."""
    perm = [(s, s + 1) for s in range(stages - 1)]

    def step_fn(params, cache, tokens, pos):
        sid = jax.lax.axis_index("pipe")
        h = M.decode_embed(params, cfg, tokens, pos)
        y = h
        for tau in range(stages):
            y, kv = M.decode_blocks(params["blocks"], local_cfg, h, cache, pos)
            cache = jax.tree_util.tree_map(
                lambda new, old: jnp.where(sid == tau, new, old), kv, cache
            )
            if tau < stages - 1:
                h = jax.lax.ppermute(y, "pipe", perm)
        logits = M.lm_logits(params, cfg, y)
        return jax.lax.psum(
            jnp.where(sid == stages - 1, logits, jnp.zeros_like(logits)),
            "pipe",
        ), cache

    return step_fn


def _build_staged_prefill(cfg: ModelConfig, stages: int, B: int, S: int,
                          capacity: int, mesh, pspecs):
    """Staged prefill: same hop structure as the decode step, on the whole
    prompt.  Only stage ``tau``'s cache write survives hop ``tau``, so the
    per-stage KV ring ends bitwise-identical to its slice of the unstaged
    cache.  Every chip runs ``S`` hops of ``L/S`` layers — replicated-
    prefill FLOPs, ``1/S`` of its memory."""
    local_cfg, cspecs = _staged_cache_specs(cfg, stages, B, capacity)
    perm = [(s, s + 1) for s in range(stages - 1)]

    def program(params, batch):
        _PREFILL_TRACES[0] += 1
        obs.get().record_compile("serve_prefill_staged", stages=stages,
                                 capacity=capacity)
        sid = jax.lax.axis_index("pipe")
        cache = M.init_cache(local_cfg, batch["tokens"].shape[0], capacity)
        h = M.prefill_embed(params, cfg, batch)
        y = h
        for tau in range(stages):
            y, kv = M.prefill_blocks(params["blocks"], local_cfg, h, cache)
            cache = jax.tree_util.tree_map(
                lambda new, old: jnp.where(sid == tau, new, old), kv, cache
            )
            if tau < stages - 1:
                h = jax.lax.ppermute(y, "pipe", perm)
        logits = M.lm_logits(params, cfg, y[:, -1:])
        logits = jax.lax.psum(
            jnp.where(sid == stages - 1, logits, jnp.zeros_like(logits)),
            "pipe",
        )
        return logits, cache

    bspecs = {"tokens": P()}
    f = jax.shard_map(
        program, mesh=mesh, in_specs=(pspecs, bspecs),
        out_specs=(P(), cspecs), check_vma=False,
    )
    return jax.jit(f)


def _build_staged_decode(cfg: ModelConfig, stages: int, B: int, S: int,
                         max_new: int, capacity: int, greedy: bool, mesh,
                         pspecs):
    local_cfg, cspecs = _staged_cache_specs(cfg, stages, B, capacity)
    step_fn = _staged_step_fn(cfg, local_cfg, stages)

    def program(params, tokens, cache, first_logits, keys, temperature):
        _DECODE_TRACES[0] += 1
        obs.get().record_compile("serve_decode_staged", stages=stages,
                                 S=S, max_new=max_new)
        nxt = _sample(first_logits, keys, 0, temperature, greedy)
        buf = jnp.zeros((B, S + max_new), jnp.int32)
        buf = jax.lax.dynamic_update_slice(buf, tokens.astype(jnp.int32), (0, 0))
        buf = buf.at[:, S].set(nxt)
        new_toks, cache = M.decode_scan(
            params, cfg, nxt, cache, S, max_new - 1,
            lambda lg, i: _sample(lg, keys, i + 1, temperature, greedy),
            step_fn=step_fn,
        )
        return jax.lax.dynamic_update_slice(buf, new_toks, (0, S + 1)), cache

    f = jax.shard_map(
        program, mesh=mesh,
        in_specs=(pspecs, P(), cspecs, P(), P(), P()),
        out_specs=(P(), cspecs), check_vma=False,
    )
    return jax.jit(f, donate_argnums=_donate((2,)))


def _staged_request(params, cfg: ModelConfig, mode: str, mesh) -> None:
    """Validate a pipe-mesh request (stage count >= 2)."""
    names = tuple(getattr(mesh, "axis_names", ()))
    extra = [a for a in names if a != "pipe" and mesh.shape[a] > 1]
    if extra:
        raise ValueError(
            f"stage-split serving wants a pipe-only mesh; axes {extra} have "
            "size > 1 (shard the batch on a separate data mesh instead)"
        )
    if mode == "ensemble":
        raise ValueError(
            "mode='ensemble' is not supported with stage-split decode: the "
            "vmapped population step and the pipe hops do not compose; "
            "serve the soup or a member on the pipe mesh"
        )
    reason = M.staged_decode_supported(cfg)
    if reason is not None:
        raise NotImplementedError(f"staged decode: {reason}")
    stages = mesh.shape["pipe"]
    if cfg.num_layers % stages:
        raise ValueError(
            f"num_layers={cfg.num_layers} does not split evenly over "
            f"{stages} pipeline stages"
        )


def _shard_staged_request(params, batch, keys, mesh, pspecs):
    """Place a staged request: blocks leaves stage-sliced over ``pipe``,
    batch/keys/other params replicated."""
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, pspecs
    )
    rep = NamedSharding(mesh, P())
    batch = {k: jax.device_put(v, rep) for k, v in batch.items()}
    keys = jax.device_put(keys, rep)
    return params, batch, keys


def _programs(cfg: ModelConfig, ensemble: bool, B: int, S: int, max_new: int,
              capacity: int, greedy: bool, mesh, stages: int = 1,
              params=None):
    """Executable-cache lookup: one (prefill, decode) pair per shape key.

    ``cfg`` is a frozen dataclass and ``mesh`` is hashable, so the key is
    exact — a new shape compiles once, every later request with the same
    key reuses the executable (0 additional traces).  ``stages > 1``
    selects the stage-split program pair (and keys the cache on it)."""
    key = ("serve", cfg, ensemble, B, S, max_new, capacity, greedy, mesh,
           stages)
    if key not in _EXEC_CACHE:
        if stages > 1:
            pspecs = _staged_param_specs(params)
            _EXEC_CACHE[key] = (
                _build_staged_prefill(cfg, stages, B, S, capacity, mesh,
                                      pspecs),
                _build_staged_decode(cfg, stages, B, S, max_new, capacity,
                                     greedy, mesh, pspecs),
            )
        else:
            _EXEC_CACHE[key] = (
                _build_prefill(cfg, ensemble, capacity),
                _build_decode(cfg, ensemble, S, max_new, greedy),
            )
    return _EXEC_CACHE[key]


def _shard_request(params, batch, keys, cfg: ModelConfig, mesh):
    """Place the request on a serving mesh: batch over the data axes,
    params (and sample keys) replicated.  GSPMD propagates the batch
    sharding through prefill/decode; the KV cache comes out batch-sharded
    without an explicit spec."""
    from repro.sharding import rules

    bspecs = rules.batch_pspecs(cfg, mesh, batch["tokens"].shape[0])
    batch = {
        k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
        for k, v in batch.items()
    }
    rep = NamedSharding(mesh, P())
    params = jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), params)
    keys = jax.device_put(keys, rep)
    return params, batch, keys


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def averaged_params(trained: Any) -> PyTree:
    """Serving params (uniform soup) from either training engine's output.

    Accepts a :class:`repro.train.loop.TrainResult` or a bare stacked
    population pytree.  The fused shard_map engine returns leaves sharded
    over the ``ens`` mesh axis; the ens-axis mean runs on the sharded
    arrays FIRST (1× model size moves, not N×), then the single averaged
    member is gathered so the serving path can feed it to
    ``prefill``/``decode_step`` on any mesh.
    """
    population = getattr(trained, "population", trained)
    soup = averaging.uniform_soup(population)
    return jax.tree_util.tree_map(_gather_leaf, soup)


def _gather_leaf(x):
    # shared multi-device predicate+gather (core.population.host_gather);
    # re-wrapped as a device array so serving never feeds numpy to jit
    return jnp.asarray(pop.host_gather(x))


def serving_params(trained: Any, mode: str = "soup", member: int = 0) -> PyTree:
    """Params for a serving mode from either training engine's output.

    soup → averaged member; member → member *i*; ensemble → the full
    stacked population (gathered off any training mesh so the serving
    programs can place it on the serving mesh)."""
    if mode not in MODES:
        raise ValueError(f"unknown serving mode {mode!r}; expected one of {MODES}")
    population = getattr(trained, "population", trained)
    if mode == "soup":
        return averaged_params(population)
    if mode == "member":
        return jax.tree_util.tree_map(
            _gather_leaf, pop.member(population, member)
        )
    return jax.tree_util.tree_map(_gather_leaf, population)


def generate_from_population(
    trained: Any,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    mode: str = "soup",
    member: int = 0,
    mesh=None,
) -> jax.Array:
    """Serve a trained population (either engine) under a serving mode."""
    return generate(
        serving_params(trained, mode, member), cfg, batch, max_new_tokens,
        temperature=temperature, key=key,
        mode="ensemble" if mode == "ensemble" else "soup", mesh=mesh,
    )


def generate(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    mode: str = "soup",
    mesh=None,
) -> jax.Array:
    """batch: {"tokens": (B,S), ["patches"|"frames"]: ...} -> (B, S+max_new).

    ``mode="soup"``/``"member"`` serve ``params`` as a single model (the
    two differ only in how the caller picked the params); ``"ensemble"``
    expects a stacked (N, ...) population and averages member logits
    in-scan.  ``mesh`` (optional) shards the batch over its data axes —
    or, with a ``("pipe",)`` mesh, stage-splits the blocks and KV cache
    over ``mesh.shape["pipe"]`` pipeline stages (bitwise-identical
    tokens, ``1/S`` the per-chip blocks+cache memory).
    """
    if mode not in MODES:
        raise ValueError(f"unknown serving mode {mode!r}; expected one of {MODES}")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    ensemble = mode == "ensemble"
    tokens = batch["tokens"]
    B, S = tokens.shape
    capacity = internal_prefix(cfg) + S + max_new_tokens
    greedy = temperature <= 0.0

    staged = mesh is not None and "pipe" in tuple(getattr(mesh, "axis_names", ()))
    stages = mesh.shape["pipe"] if staged else 1
    if stages > 1:
        _staged_request(params, cfg, mode, mesh)

    keys = _request_keys(key, B, temperature)
    if mesh is not None and stages == 1:
        params, batch, keys = _shard_request(params, batch, keys, cfg, mesh)
        tokens = batch["tokens"]

    prefill_fn, decode_fn = _programs(
        cfg, ensemble, B, S, max_new_tokens, capacity, greedy, mesh,
        stages=stages, params=params,
    )
    if stages > 1:
        params, batch, keys = _shard_staged_request(
            params, batch, keys, mesh, _staged_param_specs(params)
        )
        tokens = batch["tokens"]
    tel = obs.get()
    with tel.span("serve.prefill", S=S, B=B):
        logits, cache = prefill_fn(params, batch)
    with tel.span("serve.decode", S=S, max_new=max_new_tokens):
        out, _ = decode_fn(params, tokens, cache, logits, keys,
                           jnp.float32(max(temperature, 1e-6)))
        return out


# ---------------------------------------------------------------------------
# legacy reference loop (parity tests + serving_bench baseline)
# ---------------------------------------------------------------------------


def generate_reference(
    params: PyTree,
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    max_new_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """The pre-engine serving path, preserved verbatim in structure: a fresh
    ``jax.jit`` closure per request (so decode re-traces on EVERY call —
    count it via :func:`reference_trace_count`) and a Python loop with one
    host dispatch and a list append per token.  Sampling uses the same
    per-request fold-in scheme as the scan program, so the two paths are
    token-parity-comparable under a fixed key (tests/test_serving.py
    asserts bitwise equality).  Do not use in serving — this exists as the
    benchmark baseline and the parity oracle for :func:`generate`.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    prefix = internal_prefix(cfg)
    capacity = prefix + S + max_new_tokens
    greedy = temperature <= 0.0
    keys = _request_keys(key, B, temperature)
    temp = jnp.float32(max(temperature, 1e-6))

    logits, cache = M.prefill(params, cfg, batch, capacity=capacity)

    def _counted_decode(p, t, c, pos):
        _REFERENCE_TRACES[0] += 1
        return M.decode_step(p, cfg, t, c, pos)

    decode = jax.jit(_counted_decode)  # fresh closure: re-traced per request

    out = [tokens.astype(jnp.int32)]
    nxt = _sample(logits, keys, 0, temp, greedy)
    for i in range(max_new_tokens):
        out.append(nxt[:, None])
        if i == max_new_tokens - 1:
            break
        logits, cache = decode(params, nxt[:, None], cache, prefix + S + i)
        nxt = _sample(logits, keys, i + 1, temp, greedy)
    return jnp.concatenate(out, axis=1)
