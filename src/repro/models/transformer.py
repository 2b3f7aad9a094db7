"""Model assembly: init / train-loss / prefill / decode for every family.

Blocks are *scanned*: all layer parameters are stacked along a leading
``num_layers`` axis under ``params["blocks"]`` (and ``params["enc_blocks"]``
for encoder–decoder models).  This keeps HLO size O(1) in depth — required
for the 61/80-layer dry-runs — and the WASH layer-wise schedule stays exact
via the layered plans in ``repro.core.shuffle``.  A model whose first
``cfg.first_dense_layers`` blocks have a dense MLP before an expert stack
(DeepSeek-V2) holds them as a second stack, ``params["dense_blocks"]``:
each path runs the stacks in depth order (:func:`segments`), and the
layer-stacked caches and pools stay indexed by depth.

Batch dicts:
  dense/moe/ssm/hybrid : {"tokens": (B,S) int32}
  vlm                  : + {"patches": (B,P,D)}        (stubbed ViT output)
  audio (whisper)      : + {"frames": (B,F,D)}          (stubbed conv/mel output)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

PyTree = Any


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _mlp_init(key, cfg: ModelConfig):
    if cfg.moe:
        return MOE.moe_init(key, cfg)
    return L.swiglu_init(key, cfg.d_model, cfg.d_ff, L.param_dtype(cfg))


def _block_init(key, cfg: ModelConfig):
    dtype = L.param_dtype(cfg)
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    if cfg.block_kind == "rwkv6":
        return {
            "ln1": L.rmsnorm_init(D, dtype),
            "ln2": L.rmsnorm_init(D, dtype),
            "rwkv": SSM.rwkv6_init(ks[0], cfg),
        }
    p = {
        "ln1": L.rmsnorm_init(D, dtype),
        "ln2": L.rmsnorm_init(D, dtype),
        "mlp": _mlp_init(ks[1], cfg),
    }
    if cfg.mla:
        p["attn"] = L.mla_init(ks[0], cfg)
    else:
        p["attn"] = L.gqa_init(ks[0], cfg)
    if cfg.block_kind == "hybrid":
        p["mamba"] = SSM.mamba_init(ks[2], cfg)
        p["beta"] = jnp.ones((2,), jnp.float32)  # learned attn/ssm fusion
    return p


def _enc_block_init(key, cfg: ModelConfig):
    dtype = L.param_dtype(cfg)
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.gqa_init(ks[0], cfg),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "mlp": L.gelu_mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype),
    }


def _dec_block_init(key, cfg: ModelConfig):
    p = _block_init(key, cfg)
    p["xattn"] = L.xattn_init(jax.random.fold_in(key, 99), cfg)
    p["ln_x"] = L.rmsnorm_init(cfg.d_model, L.param_dtype(cfg))
    return p


def init_params(key, cfg: ModelConfig) -> PyTree:
    dtype = L.param_dtype(cfg)
    ks = jax.random.split(key, 8)
    D, V = cfg.d_model, cfg.vocab_size
    params: Dict[str, Any] = {
        "embed": {"tok": L.dense_init(ks[0], (V, D), dtype, scale=0.02)},
        "final_norm": L.rmsnorm_init(D, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": L.dense_init(ks[1], (D, V), dtype)}
    if cfg.pos_kind == "learned":
        params["embed"]["pos"] = L.dense_init(
            ks[2], (cfg.max_position, D), dtype, scale=0.02
        )
    if cfg.frontend == "vision":
        params["embed"]["patch_proj"] = L.dense_init(ks[3], (D, D), dtype)
    if cfg.frontend == "audio":
        params["embed"]["frame_proj"] = L.dense_init(ks[3], (D, D), dtype)
        params["embed"]["enc_pos"] = L.dense_init(
            ks[4], (cfg.num_frames, D), dtype, scale=0.02
        )

    block_init = _dec_block_init if cfg.is_encdec else _block_init
    bkeys = jax.random.split(ks[5], cfg.num_layers)
    k = cfg.first_dense_layers
    if k:
        dense = _dense_cfg(cfg)
        params["dense_blocks"] = jax.vmap(lambda kk: block_init(kk, dense))(
            bkeys[:k])
    params["blocks"] = jax.vmap(lambda kk: block_init(kk, cfg))(bkeys[k:])
    if cfg.is_encdec:
        ekeys = jax.random.split(ks[6], cfg.encoder_layers)
        params["enc_blocks"] = jax.vmap(lambda k: _enc_block_init(k, cfg))(ekeys)
        params["enc_norm"] = L.rmsnorm_init(D, dtype)
    return params


# ---------------------------------------------------------------------------
# stacks of like blocks
# ---------------------------------------------------------------------------


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    """The configuration of the leading dense blocks: a SwiGLU of width
    ``d_ff`` in place of the experts."""
    return dataclasses.replace(cfg, moe=False, first_dense_layers=0)


def segments(params, cfg: ModelConfig) -> List[Tuple[Any, ModelConfig, int]]:
    """``(blocks, cfg, first layer)`` of each stack of like blocks, in
    depth order: the leading dense blocks (if any), then ``blocks``."""
    k = cfg.first_dense_layers
    segs = [(params["dense_blocks"], _dense_cfg(cfg), 0)] if k else []
    return segs + [(params["blocks"], cfg, k)]


def _stack_len(blocks) -> int:
    return jax.tree_util.tree_leaves(blocks)[0].shape[0]


def _scan_segments(params, cfg: ModelConfig, x, cache, make_body):
    """Scan each stack with ``make_body(stack_cfg)`` over ``(block,
    cache slice)``, the layer-stacked ``cache`` cut to the stack's depths
    and put back together.  Returns ``(x, cache)``."""
    outs = []
    for blocks, scfg, first in segments(params, cfg):
        n = _stack_len(blocks)
        part = jax.tree_util.tree_map(lambda c: c[first:first + n], cache)
        x, new = jax.lax.scan(make_body(scfg), x, (blocks, part),
                              unroll=cfg.scan_unroll)
        outs.append(new)
    return x, jax.tree_util.tree_map(
        lambda *c: jnp.concatenate(c, axis=0), *outs)


# ---------------------------------------------------------------------------
# block application (full sequence)
# ---------------------------------------------------------------------------


def _mlp_apply(p, cfg: ModelConfig, x):
    with jax.named_scope("mlp"):
        if cfg.moe:
            return MOE.moe_apply(p, cfg, x)
        return L.swiglu(p, x), jnp.zeros((), jnp.float32)


def _block_train(p, cfg: ModelConfig, x, state_l=None):
    """Returns (x, new_state_l, aux)."""
    if cfg.block_kind == "rwkv6":
        x, new_state = SSM.rwkv6_block(
            p["rwkv"], cfg, x, state_l, {"ln1": p["ln1"], "ln2": p["ln2"]}
        )
        return x, new_state, jnp.zeros((), jnp.float32)

    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        if cfg.mla:
            a = L.mla_train(p["attn"], cfg, h)
        else:
            a = L.gqa_train(p["attn"], cfg, h)
    if cfg.block_kind == "hybrid":
        m, new_ssm = SSM.mamba_prefill(p["mamba"], cfg, h, state_l)
        beta = jax.nn.softmax(p["beta"]).astype(a.dtype)
        a = beta[0] * a + beta[1] * m
    else:
        new_ssm = state_l
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    y, aux = _mlp_apply(p["mlp"], cfg, h)
    return x + y, new_ssm, aux


def _run_blocks_train(params, cfg: ModelConfig, x):
    """Scan all decoder-only blocks over the stacked layer axis."""
    B = x.shape[0]
    if cfg.block_kind == "rwkv6":
        init_state = SSM.rwkv_state_init(cfg, B, cfg.num_layers)
    elif cfg.block_kind == "hybrid":
        init_state = SSM.mamba_state_init(cfg, B, cfg.num_layers)
    else:
        init_state = None

    if init_state is not None:
        def body(h, xs):
            block_l, state_l = xs
            h, _, aux = _block_train(block_l, cfg, h, state_l)
            return h, aux

        if cfg.remat_blocks:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, (params["blocks"], init_state),
                               unroll=cfg.scan_unroll)
        return x, jnp.sum(auxs)

    total = None
    for blocks, scfg, _ in segments(params, cfg):
        def body(h, block_l, scfg=scfg):
            h, _, aux = _block_train(block_l, scfg, h, None)
            return h, aux

        if cfg.remat_blocks:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, blocks, unroll=cfg.scan_unroll)
        total = jnp.sum(auxs) if total is None else total + jnp.sum(auxs)
    return x, total


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------


def _embed_tokens(params, cfg: ModelConfig, tokens, pos0: int = 0):
    x = params["embed"]["tok"][tokens]
    if cfg.pos_kind == "learned":
        T = tokens.shape[1]
        pos = jax.lax.dynamic_slice_in_dim(params["embed"]["pos"], pos0, T, 0)
        x = x + pos[None]
    return x


def _logits(params, cfg: ModelConfig, x):
    with jax.named_scope("logits"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return x @ params["embed"]["tok"].T
        return x @ params["lm_head"]["w"]


def _encode(params, cfg: ModelConfig, frames):
    """Whisper-style encoder over stubbed frame embeddings (B,F,D)."""
    x = frames @ params["embed"]["frame_proj"] + params["embed"]["enc_pos"][None]

    def body(h, block_l):
        a = L.gqa_train(
            block_l["attn"], cfg, L.rmsnorm(block_l["ln1"], h, cfg.norm_eps),
            bidirectional=True,
        )
        h = h + a
        y = L.gelu_mlp(block_l["mlp"], L.rmsnorm(block_l["ln2"], h, cfg.norm_eps))
        return h + y, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"], unroll=cfg.scan_unroll)
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _run_dec_blocks_train(params, cfg: ModelConfig, x, enc_out):
    def body(h, block_l):
        a = L.gqa_train(block_l["attn"], cfg, L.rmsnorm(block_l["ln1"], h, cfg.norm_eps))
        h = h + a
        c = L.xattn(block_l["xattn"], cfg, L.rmsnorm(block_l["ln_x"], h, cfg.norm_eps), enc_out)
        h = h + c
        y, _ = _mlp_apply(block_l["mlp"], cfg, L.rmsnorm(block_l["ln2"], h, cfg.norm_eps))
        return h + y, None

    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=cfg.scan_unroll)
    return x


# ---------------------------------------------------------------------------
# public API: train / eval
# ---------------------------------------------------------------------------


def forward_logits(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits (B,S,V) over the *text* positions + aux loss."""
    tokens = batch["tokens"]
    aux = jnp.zeros((), jnp.float32)

    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["frames"])
        x = _embed_tokens(params, cfg, tokens)
        x = _run_dec_blocks_train(params, cfg, x, enc_out)
        return _logits(params, cfg, x), aux

    x = _embed_tokens(params, cfg, tokens)
    n_prefix = 0
    if cfg.frontend == "vision":
        patches = batch["patches"] @ params["embed"]["patch_proj"]
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        n_prefix = patches.shape[1]
    x, aux = _run_blocks_train(params, cfg, x)
    if n_prefix:
        x = x[:, n_prefix:]
    return _logits(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (+ router aux loss for MoE)."""
    logits, aux = forward_logits(params, cfg, batch)
    targets = batch["tokens"][:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    total = loss + cfg.router_aux_coef * aux
    return total, {"nll": loss, "aux": aux}


def pipeline_supported(cfg: ModelConfig) -> Optional[str]:
    """None if the pipelined training engine can stage-split this config,
    else the reason.  The stage boundary carries ONE activation tensor, so
    anything with extra cross-block state (SSM/hybrid recurrences, the
    encoder output of enc-dec, modality prefixes) or a cross-stage loss
    term (the MoE router aux, summed over *all* layers) is rejected loudly
    rather than trained wrong."""
    if cfg.block_kind != "attn":
        return f"block_kind={cfg.block_kind!r} carries state across blocks"
    if cfg.first_dense_layers:
        return "leading dense blocks are a stack of their own"
    if cfg.is_encdec:
        return "encoder-decoder needs the encoder output on every stage"
    if cfg.frontend is not None:
        return f"frontend={cfg.frontend!r} prefixes are not stage-split"
    if cfg.moe:
        return "MoE router aux loss is not accumulated across stages"
    return None


def pipeline_stage_fns(cfg: ModelConfig):
    """(embed, blocks, head) callables for
    :func:`repro.train.engine.train_population_pipelined` (its
    ``StageFns``).  ``blocks`` scans whatever slice of ``params["blocks"]``
    the engine hands it, so the same function serves every stage.  The
    composition ``head(blocks(embed(..)))`` equals :func:`loss_fn`'s nll
    for the supported (attn, non-MoE) families."""
    reason = pipeline_supported(cfg)
    if reason is not None:
        raise NotImplementedError(f"pipelined training: {reason}")

    def embed(params, batch):
        return _embed_tokens(params, cfg, batch["tokens"])

    def blocks(params, x):
        def body(h, block_l):
            h, _, _ = _block_train(block_l, cfg, h, None)
            return h, None

        if cfg.remat_blocks:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["blocks"], unroll=cfg.scan_unroll)
        return x

    def head(params, x, batch):
        logits = _logits(params, cfg, x)
        targets = batch["tokens"][:, 1:]
        lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        return jnp.mean(-jnp.take_along_axis(lp, targets[..., None], axis=-1)[..., 0])

    return embed, blocks, head


# ---------------------------------------------------------------------------
# public API: serving (prefill + one-token decode)
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, capacity: int) -> PyTree:
    """Decode-state pytree.  ``capacity`` = logical context; sliding-window
    archs allocate only ``min(window, capacity)`` KV slots."""
    cache: Dict[str, Any] = {}
    Lc = cfg.num_layers
    if cfg.block_kind == "rwkv6":
        cache["state"] = SSM.rwkv_state_init(cfg, batch, Lc)
        return cache
    cap = capacity if cfg.window is None else min(cfg.window, capacity)
    if cfg.mla:
        cache["kv"] = L.mla_cache_init(cfg, batch, cap, Lc)
    else:
        cache["kv"] = L.gqa_cache_init(cfg, batch, cap, Lc)
    if cfg.block_kind == "hybrid":
        cache["ssm"] = SSM.mamba_state_init(cfg, batch, Lc)
    if cfg.is_encdec:
        hd = cfg.resolved_head_dim
        dt = L.param_dtype(cfg)
        cache["xk"] = jnp.zeros((Lc, batch, cfg.num_frames, cfg.num_kv_heads, hd), dt)
        cache["xv"] = jnp.zeros((Lc, batch, cfg.num_frames, cfg.num_kv_heads, hd), dt)
    return cache


def _block_decode(block_l, cfg: ModelConfig, x, cache_l, pos):
    """One-token decode for one (scanned) layer. Returns (x, new_cache_l)."""
    new_cache = dict(cache_l)
    if cfg.block_kind == "rwkv6":
        x, new_state = SSM.rwkv6_block(
            block_l["rwkv"], cfg, x, cache_l["state"],
            {"ln1": block_l["ln1"], "ln2": block_l["ln2"]},
        )
        new_cache["state"] = new_state
        return x, new_cache

    h = L.rmsnorm(block_l["ln1"], x, cfg.norm_eps)
    if cfg.mla:
        a, new_cache["kv"] = L.mla_decode(block_l["attn"], cfg, h, cache_l["kv"], pos)
    else:
        a, new_cache["kv"] = L.gqa_decode(block_l["attn"], cfg, h, cache_l["kv"], pos)
    if cfg.block_kind == "hybrid":
        m, new_cache["ssm"] = SSM.mamba_decode(block_l["mamba"], cfg, h, cache_l["ssm"])
        beta = jax.nn.softmax(block_l["beta"]).astype(a.dtype)
        a = beta[0] * a + beta[1] * m
    x = x + a
    if cfg.is_encdec:
        hx = L.rmsnorm(block_l["ln_x"], x, cfg.norm_eps)
        B = x.shape[0]
        hd = cfg.resolved_head_dim
        q = (hx @ block_l["xattn"]["wq"]).reshape(B, 1, cfg.num_heads, hd)
        mask = jnp.ones((1, cache_l["xk"].shape[1]), bool)
        c = L.sdpa(q, cache_l["xk"], cache_l["xv"], mask, cfg.num_kv_heads)
        x = x + c.reshape(B, 1, -1) @ block_l["xattn"]["wo"]
    h = L.rmsnorm(block_l["ln2"], x, cfg.norm_eps)
    y, _ = _mlp_apply(block_l["mlp"], cfg, h)
    return x + y, new_cache


def decode_step(params, cfg: ModelConfig, tokens, cache, pos):
    """serve_step: ONE new token (B,1) against the cache at position ``pos``.

    ``pos`` may be a Python int or a traced scalar: every cache update is a
    ``dynamic_update``/ring-slot op, so the serving engine can drive this
    from a ``lax.scan`` over token positions without shape specialization.
    """
    pos = jnp.asarray(pos, jnp.int32)
    x = _embed_tokens(params, cfg, tokens, pos0=pos) if cfg.pos_kind == "learned" else (
        params["embed"]["tok"][tokens]
    )

    def make_body(scfg):
        def body(h, xs):
            block_l, cache_l = xs
            h, new_cache_l = _block_decode(block_l, scfg, h, cache_l, pos)
            return h, new_cache_l
        return body

    x, new_cache = _scan_segments(params, cfg, x, cache, make_body)
    return _logits(params, cfg, x), new_cache


def staged_decode_supported(cfg: ModelConfig) -> Optional[str]:
    """None if the stage-split (pipeline) serving path can serve this
    config, else the reason.

    Stage-split decode slices ``params["blocks"]`` (and the layer-leading
    KV cache) over a ``pipe`` mesh axis and moves the activation between
    stages with ``ppermute``.  That only composes cleanly for the plain
    attention families whose entire decode state is the layer-stacked KV
    ring: SSM/hybrid recurrent state and the encoder-decoder cross cache
    carry extra per-layer leaves the staged cache plumbing does not split,
    and modality prefixes (vision patches) make the prefill embedding
    stage-dependent.  All rejected loudly rather than served wrong."""
    if cfg.block_kind != "attn":
        return f"block_kind={cfg.block_kind!r} state is not stage-split"
    if cfg.first_dense_layers:
        return "leading dense blocks are a stack of their own"
    if cfg.is_encdec:
        return "encoder-decoder cross-attention cache is not stage-split"
    if cfg.frontend is not None:
        return f"frontend={cfg.frontend!r} prefixes are not stage-split"
    return None


def decode_embed(params, cfg: ModelConfig, tokens, pos):
    """The embedding half of :func:`decode_step` (staged serving runs it on
    every stage — embed params are pipe-replicated, so all stages agree)."""
    pos = jnp.asarray(pos, jnp.int32)
    if cfg.pos_kind == "learned":
        return _embed_tokens(params, cfg, tokens, pos0=pos)
    return params["embed"]["tok"][tokens]


def decode_blocks(blocks, cfg: ModelConfig, x, cache, pos):
    """One-token decode through a contiguous slice of blocks.

    ``blocks``/``cache`` hold ``cfg.num_layers`` layers — the staged
    serving engine passes its per-stage slice with a ``num_layers``-patched
    config.  Scanning a slice composes bitwise with scanning the full
    stack, which is what the staged-vs-unstaged parity contract rests on.
    Returns ``(x, new_cache)``."""
    pos = jnp.asarray(pos, jnp.int32)

    def body(h, xs):
        block_l, cache_l = xs
        h, new_cache_l = _block_decode(block_l, cfg, h, cache_l, pos)
        return h, new_cache_l

    return jax.lax.scan(body, x, (blocks, cache), unroll=cfg.scan_unroll)


def prefill_embed(params, cfg: ModelConfig, batch):
    """Prompt embedding for the staged prefill (attn-only families — the
    vision/audio prefixes are rejected by :func:`staged_decode_supported`)."""
    return _embed_tokens(params, cfg, batch["tokens"])


def prefill_blocks(blocks, cfg: ModelConfig, x, cache):
    """Full-prompt prefill through a contiguous slice of blocks.

    Per-layer ops are the exact sequence of :func:`prefill`'s scan body
    restricted to the attn families, so stage-slicing preserves bitwise
    parity with the single-scan prefill.  Returns ``(x, new_cache)``."""

    def body(h, xs):
        block_l, cache_l = xs
        new_cache_l = dict(cache_l)
        a_in = L.rmsnorm(block_l["ln1"], h, cfg.norm_eps)
        if cfg.mla:
            a, new_cache_l["kv"] = L.mla_prefill(
                block_l["attn"], cfg, a_in, cache_l["kv"])
        else:
            a, new_cache_l["kv"] = L.gqa_prefill(
                block_l["attn"], cfg, a_in, cache_l["kv"])
        h = h + a
        y, _ = _mlp_apply(block_l["mlp"], cfg,
                          L.rmsnorm(block_l["ln2"], h, cfg.norm_eps))
        return h + y, new_cache_l

    return jax.lax.scan(body, x, (blocks, cache), unroll=cfg.scan_unroll)


def lm_logits(params, cfg: ModelConfig, x):
    """Final-norm + LM head (public alias of the private ``_logits`` for
    the staged serving engine, which runs the head on the last stage)."""
    return _logits(params, cfg, x)


def paged_decode_supported(cfg: ModelConfig) -> Optional[str]:
    """None if ``decode_step_paged`` can serve this config, else the reason.

    The paged path covers the decoder-only attention families (dense +
    MoE, grouped-query or latent attention, leading dense blocks).
    SSM/hybrid state is not paged, sliding windows interact with page
    retirement, and encoder-decoder / modality prefixes need prefix-page
    plumbing — all future work, all rejected loudly rather than served
    wrong."""
    if cfg.block_kind != "attn":
        return f"block_kind={cfg.block_kind!r} state is not paged"
    if cfg.is_encdec:
        return "encoder-decoder cross-attention cache is not paged"
    if cfg.frontend is not None:
        return f"frontend={cfg.frontend!r} prefixes are not paged"
    if cfg.window is not None:
        return "sliding-window ring eviction is not paged"
    return None


def _paged_layers(params, cfg: ModelConfig, x, pools, attend):
    """Every block over x against the whole paged ``pools``: layer ``i``
    of each stack attends through ``attend(attn_params, cfg, h, pools,
    depth)`` at its depth in the pools, which it updates in place, and
    the pools are carried through the layer loop.  Weights are indexed by
    the loop's layer rather than scanned: an ensemble vmaps this over
    members, and scanning (N, L, ...) weights would transpose all of them
    to (L, N, ...) on every call.  Returns ``(x, pools)``."""
    for blocks, scfg, first in segments(params, cfg):
        def body(carry, i, blocks=blocks, scfg=scfg, first=first):
            h, pools = carry
            block_l = jax.tree_util.tree_map(lambda w: w[i], blocks)
            a_in = L.rmsnorm(block_l["ln1"], h, cfg.norm_eps)
            with jax.named_scope("attention"):
                a, pools = attend(block_l["attn"], scfg, a_in, pools,
                                  first + i)
            h = h + a
            y, _ = _mlp_apply(block_l["mlp"], scfg,
                              L.rmsnorm(block_l["ln2"], h, cfg.norm_eps))
            return (h + y, pools), None

        (x, pools), _ = jax.lax.scan(
            body, (x, pools), jnp.arange(_stack_len(blocks), dtype=jnp.int32),
            unroll=cfg.scan_unroll)
    return x, pools


def decode_step_paged(params, cfg: ModelConfig, tokens, positions, pools,
                      page_tables, use_pallas: bool = False):
    """One decode token for a batch of serving *slots* over the paged pool.

      tokens      : (B,) int32 — one new token id per slot
      positions   : (B,) int32 — each token's absolute write position
                    (per-slot, unlike :func:`decode_step`'s shared scalar —
                    slots in a continuous batch sit at different depths)
      pools       : the pool pytree of
                    :func:`repro.models.layers.paged_pools_init`:
                    {"k","v"} (L, P, page_size * KV, hd), or MLA's
                    {"latent"} (L, P, page_size, r + qk_rope)
      page_tables : (B, max_pages) int32 pool-page ids per slot

    Everything is traced — admissions, retirements, and page-table edits
    change VALUES only, so the continuous-batching runtime compiles this
    exactly once per pool geometry.  The layer loop carries the whole
    pools (the layer index is the scan's input) and each layer writes
    only its B new rows into them, so a program that donates the pools
    updates them in place: no layer's pool is sliced out, relaid out or
    written back.  Returns ``(logits (B,1,V), pools)``.
    """
    reason = paged_decode_supported(cfg)
    if reason is not None:
        raise NotImplementedError(f"paged decode: {reason}")
    pos = jnp.asarray(positions, jnp.int32)
    x = params["embed"]["tok"][tokens[:, None]]
    if cfg.pos_kind == "learned":
        x = x + params["embed"]["pos"][pos][:, None]
    attend_paged = L.mla_decode_paged if cfg.mla else L.gqa_decode_paged

    def attend(p, scfg, h, pools, layer):
        return attend_paged(p, scfg, h, pools, layer, page_tables, pos,
                            use_pallas)

    x, pools = _paged_layers(params, cfg, x, pools, attend)
    return _logits(params, cfg, x), pools


def paged_prefill_supported(cfg: ModelConfig) -> Optional[str]:
    """None if ``prefill_paged`` can serve this config, else the reason.

    Everything :func:`paged_decode_supported` rejects, plus non-naive
    grouped-query attention: ``attn_impl="chunked"`` prefills through the
    online-softmax formulation whose numerics differ from the paged
    gather+sdpa attend, so suffix/chunk prefill could not keep the bitwise
    parity contract.  (MLA's chunk attend does not depend on
    ``attn_impl``.)"""
    reason = paged_decode_supported(cfg)
    if reason is not None:
        return reason
    if cfg.attn_impl != "naive" and not cfg.mla:
        return (f"attn_impl={cfg.attn_impl!r} prefill numerics are not "
                "bitwise-compatible with the paged gather+sdpa attend")
    return None


def prefill_paged(params, cfg: ModelConfig, tokens, pos0, pools, page_table):
    """Chunk/suffix prefill for ONE serving slot over the paged pool.

      tokens     : (T,) int32 — a contiguous slice of the prompt
      pos0       : int or traced scalar — absolute position of ``tokens[0]``
                   (traced by the serving runtime, so ONE compile per chunk
                   length serves every offset and every slot)
      pools      : the pool pytree (:func:`decode_step_paged`), carried
                   whole through the layer loop and written in place (only
                   the chunk's rows)
      page_table : (max_pages,) int32 — the slot's pages in prompt order;
                   entries below ``pos0 // page_size`` may be chain-hash
                   shared prefix pages (read, never written)

    Earlier context — a deduped prefix and/or previously prefilled chunks —
    is read straight from the pool, so a suffix admission skips the cached
    prefix's FLOPs entirely.  Returns ``(logits (1,1,V) for the chunk's
    last position, pools)``; for grouped-query attention the rows
    written/read are bitwise-identical to the whole-prompt :func:`prefill`
    (see ``gqa_prefill_paged``)."""
    reason = paged_prefill_supported(cfg)
    if reason is not None:
        raise NotImplementedError(f"paged prefill: {reason}")
    pos0 = jnp.asarray(pos0, jnp.int32)
    T = tokens.shape[0]
    positions = pos0 + jnp.arange(T, dtype=jnp.int32)
    x = _embed_tokens(params, cfg, tokens[None], pos0=pos0)
    attend_paged = L.mla_prefill_paged if cfg.mla else L.gqa_prefill_paged

    def attend(p, scfg, h, pools, layer):
        return attend_paged(p, scfg, h, pools, layer, page_table, positions)

    x, pools = _paged_layers(params, cfg, x, pools, attend)
    return _logits(params, cfg, x[:, -1:]), pools


def decode_scan(params, cfg: ModelConfig, first, cache, start_pos, num_steps,
                next_fn, step_fn=None):
    """Fused multi-token decode: ONE ``lax.scan`` over token positions.

    Runs ``num_steps`` decode steps starting at absolute position
    ``start_pos`` (a Python int or traced scalar).  The cache threads
    through the scan carry, so the whole generation lowers to a single
    executable — the serving engine jits this once per shape instead of
    dispatching (and historically re-tracing) per token.

      first    : (B,) int32 — token ids fed to the first decode step
      next_fn  : (logits (B,1,V), step i) -> (B,) int32 next token ids
                 (sampling lives in the serving layer: greedy / temperature
                 with per-request keys / ensemble voting all plug in here)
      step_fn  : optional override of :func:`decode_step` with signature
                 (params, cache, tokens (B,1), pos) -> (logits, cache);
                 the serving engine's ensemble mode passes a vmapped
                 population step that averages member logits.

    Returns ``(tokens (B, num_steps) int32, final cache)``; ``tokens[:, i]``
    is the id sampled *after* the step at position ``start_pos + i``.
    """
    if step_fn is None:
        def step_fn(p, c, t, pos):  # noqa: E306
            return decode_step(p, cfg, t, c, pos)
    start_pos = jnp.asarray(start_pos, jnp.int32)

    def body(carry, i):
        nxt, c = carry
        logits, c = step_fn(params, c, nxt[:, None], start_pos + i)
        new = next_fn(logits, i)
        return (new, c), new

    (_, cache), toks = jax.lax.scan(
        body, (first, cache), jnp.arange(num_steps, dtype=jnp.int32)
    )
    return jnp.moveaxis(toks, 0, 1), cache


def prefill(params, cfg: ModelConfig, batch, capacity: Optional[int] = None):
    """Process the full prompt, returning (last-token logits, filled cache)."""
    tokens = batch["tokens"]
    B, T = tokens.shape
    cap = capacity or T
    cache = init_cache(cfg, B, cap)

    if cfg.block_kind == "rwkv6":
        x = _embed_tokens(params, cfg, tokens)

        def body(h, xs):
            block_l, state_l = xs
            h, new_state = SSM.rwkv6_block(
                block_l["rwkv"], cfg, h, state_l,
                {"ln1": block_l["ln1"], "ln2": block_l["ln2"]},
            )
            return h, new_state

        x, new_state = jax.lax.scan(body, x, (params["blocks"], cache["state"]), unroll=cfg.scan_unroll)
        return _logits(params, cfg, x[:, -1:]), {"state": new_state}

    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["frames"])
        hd = cfg.resolved_head_dim
        S = enc_out.shape[1]

    x = _embed_tokens(params, cfg, tokens)
    n_prefix = 0
    if cfg.frontend == "vision":
        patches = batch["patches"] @ params["embed"]["patch_proj"]
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        n_prefix = patches.shape[1]

    def make_body(cfg):
        def body(h, xs):
            block_l, cache_l = xs
            new_cache_l = dict(cache_l)
            a_in = L.rmsnorm(block_l["ln1"], h, cfg.norm_eps)
            if cfg.mla:
                a, new_cache_l["kv"] = L.mla_prefill(block_l["attn"], cfg, a_in, cache_l["kv"])
            else:
                a, new_cache_l["kv"] = L.gqa_prefill(block_l["attn"], cfg, a_in, cache_l["kv"])
            if cfg.block_kind == "hybrid":
                m, new_cache_l["ssm"] = SSM.mamba_prefill(block_l["mamba"], cfg, a_in, cache_l["ssm"])
                beta = jax.nn.softmax(block_l["beta"]).astype(a.dtype)
                a = beta[0] * a + beta[1] * m
            h = h + a
            if cfg.is_encdec:
                hx = L.rmsnorm(block_l["ln_x"], h, cfg.norm_eps)
                c = L.xattn(block_l["xattn"], cfg, hx, enc_out)
                h = h + c
                B_, = (h.shape[0],)
                new_cache_l["xk"] = (enc_out @ block_l["xattn"]["wk"]).reshape(
                    B_, S, cfg.num_kv_heads, hd
                ).astype(cache_l["xk"].dtype)
                new_cache_l["xv"] = (enc_out @ block_l["xattn"]["wv"]).reshape(
                    B_, S, cfg.num_kv_heads, hd
                ).astype(cache_l["xv"].dtype)
            y, _ = _mlp_apply(block_l["mlp"], cfg, L.rmsnorm(block_l["ln2"], h, cfg.norm_eps))
            return h + y, new_cache_l
        return body

    x, new_cache = _scan_segments(params, cfg, x, cache, make_body)
    return _logits(params, cfg, x[:, -1:]), new_cache
