"""Population-powered speculative decoding for the continuous runtime.

WASH maintains a *population* whose uniform soup and whose logit-averaged
ensemble are both strong predictors — and, because shuffling keeps the
members in one loss basin (the PAPA/WASH premise), the soup's next-token
argmax usually agrees with the ensemble's.  Ensemble-mode decode pays N
member forward passes per emitted token; this module turns the population
structure into latency instead:

  1. **Draft** — the soup (one model, the cheap predictor) runs ``k``
     ordinary paged decode steps over its OWN draft pools, proposing
     ``d_1 .. d_{k-1}`` continuation tokens per slot.
  2. **Verify** — the vmapped ensemble runs ONE teacher-forced paged
     decode step over ``B·k`` flattened rows: row ``(b, j)`` feeds input
     ``i_j`` (the pending token for ``j = 0``, draft ``d_j`` after) at
     position ``pos_b + j`` through slot ``b``'s page table.  Because
     the paged attend scatters every row's K/V **before** attending, row
     ``j`` sees its sibling rows' keys/values exactly as ``j`` sequential
     steps would have written them — per-row the batched verify is
     bitwise the sequential decode.
  3. **Accept** — the verified token ``v_j`` is what non-speculative
     decode would have emitted at output index ``steps + j`` GIVEN inputs
     ``i_0..i_j`` were the true context; the longest prefix where each
     draft matched the previous verified token (``d_j == v_{j-1}``) is
     emitted, ``m = 1 + |prefix|`` tokens per slot per call.

**Bitwise contract** (``tests/test_speculative_properties.py``): at fp32
KV, the emitted stream is bit-identical to non-speculative decode — for
greedy AND temperature sampling, since ``v_j`` is sampled with the same
deterministic ``fold_in(key_b, steps_b + j)`` the plain path uses.
Rejected rows leave *stale* K/V at positions ``>= pos + m`` in both
pools; they are invisible (every later attend masks by its own length)
and are overwritten with identical values before any row can read them.
The host rolls page tables back via ``ContinuousServer._shrink``.

Everything here is **traced**: draft length ``k`` is the only new
executable-cache key component (``("continuous", ..., kv_dtype,
draft_k)``), so warm speculative streams add zero traces — the
trace-count contract of ``serving.batching`` extends unchanged.

int8 pools compose (draft and verify pools both quantize); the bitwise
claim then relaxes to the pinned tolerance of the quantized oracle,
because a page's scale couples every row written to it.

MoE configs are rejected: capacity-factor dispatch makes a token's
routing depend on its *batchmates*, which breaks the per-row argument
above (and the continuous runtime's solo-parity contract with it).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import averaging
from repro.models import transformer as M

#: draft lengths the property suite exercises; larger k is legal but the
#: verify step's B*k rows grow the decode program linearly
MAX_DRAFT_K = 8


def speculative_supported(cfg: ModelConfig) -> Optional[str]:
    """None if speculative decode can serve ``cfg``, else the reason.

    Needs everything suffix/chunk prefill needs (the draft pools are
    populated by the same chunk programs), grouped-query attention (the
    latent pool has no draft side), and dense MLPs: a ``B·k``-row verify
    step must be bitwise the ``k`` sequential ``B``-row steps it
    replaces, which the expert layer's grouped matmuls are not shown to
    be."""
    reason = M.paged_prefill_supported(cfg)
    if reason is not None:
        return reason
    if cfg.mla:
        return ("the MLA latent pool has no draft side: the verify step's "
                "B*k rows are served by the grouped-query path only")
    if cfg.moe:
        return ("MoE: the expert layer's grouped matmuls over the verify "
                "step's B*k rows are not shown bitwise equal to k steps of "
                "B rows")
    return None


def build_speculative_decode(cfg: ModelConfig, ensemble: bool, greedy: bool,
                             use_pallas: bool, draft_k: int):
    """The speculative continuous decode step (untraced; the runtime wraps
    it with ``jax.jit`` + donation + trace counters).

    ``program(params, draft_params, pools, dpools, tokens, positions,
    steps, budgets, active, page_tables, keys, temperature)`` returns
    ``(sampled (B, k), counts (B,), done (B,), pools, dpools)`` —
    ``sampled[b, :counts[b]]`` are the emitted tokens; entries past
    ``counts`` are zero-masked.

    ``params``/``pools`` are the verify side (stacked population when
    ``ensemble``); ``draft_params``/``dpools`` the single-model draft
    side.  Page tables are SHARED: the draft pools mirror the verify
    pools' geometry and page allocation, so one host page is one logical
    context slice in both.
    """
    # late import: batching imports this module lazily from its program
    # builder, so a module-level import back would be circular
    from repro.serving.batching import _sample_steps

    if draft_k < 1:
        raise ValueError(f"draft_k must be >= 1, got {draft_k}")
    reason = speculative_supported(cfg)
    if reason is not None:
        raise NotImplementedError(f"speculative decode: {reason}")
    k = int(draft_k)

    def program(params, draft_params, pools, dpools, tokens, positions,
                steps, budgets, active, page_tables, keys, temperature):
        B = tokens.shape[0]
        # proposals this call may emit per slot: never past the budget,
        # so speculative writes stay inside the page reservation
        # (max write pos == the plain path's prompt_len + max_new - 2)
        n_valid = jnp.where(active, jnp.clip(budgets - steps, 0, k), 0)

        def masked(valid, pos, tables):
            # invalid rows write to (scratch page, offset 0) and read a
            # 1-token scratch context — garbage in, masked garbage out
            return (jnp.where(valid, pos, 0),
                    jnp.where(valid[:, None], tables,
                              jnp.zeros_like(tables)))

        # -- draft: k sequential soup steps over the draft pools --------
        # step j feeds input i_j at pos+j (writing its draft K/V) and
        # samples d_{j+1} = the soup's guess for output index steps+j
        inputs = []
        cur = tokens
        for j in range(k):
            pos_j, tab_j = masked(j < n_valid, positions + j, page_tables)
            lg, dpools = M.decode_step_paged(
                draft_params, cfg, cur, pos_j, dpools, tab_j, use_pallas,
            )
            inputs.append(cur)
            cur = _sample_steps(lg[:, -1], keys, steps + j, temperature,
                                greedy)
        inputs = jnp.stack(inputs, axis=1)            # (B, k): i_0..i_{k-1}

        # -- verify: ONE ensemble step over B*k teacher-forced rows -----
        valid2d = jnp.arange(k)[None, :] < n_valid[:, None]   # (B, k)
        pos2d = positions[:, None] + jnp.arange(k)[None, :]
        vpos, vtab = masked(valid2d.reshape(-1), pos2d.reshape(-1),
                            jnp.repeat(page_tables, k, axis=0))
        vtok = inputs.reshape(B * k)
        if ensemble:
            lgs, pools = jax.vmap(lambda p, pl_: M.decode_step_paged(
                p, cfg, vtok, vpos, pl_, vtab, use_pallas,
            ))(params, pools)
            logits = averaging.balanced_mean(lgs)     # (B*k, 1, V)
        else:
            logits, pools = M.decode_step_paged(
                params, cfg, vtok, vpos, pools, vtab, use_pallas,
            )
        lg2d = logits[:, -1].reshape(B, k, -1)
        # v_j sampled exactly as the plain path samples output steps+j
        v = jnp.stack(
            [_sample_steps(lg2d[:, j], keys, steps + j, temperature, greedy)
             for j in range(k)], axis=1)              # (B, k)

        # -- accept the longest matching prefix -------------------------
        # i_{j+1} (= draft d_{j+1}) correct  <=>  it equals v_j
        match = (inputs[:, 1:] == v[:, :k - 1]).astype(jnp.int32)
        m = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        m = jnp.minimum(m, jnp.maximum(n_valid, 1))
        counts = jnp.where(active, m, 0)
        sampled = jnp.where(valid2d & (jnp.arange(k)[None, :] < m[:, None]),
                            v, 0)
        done = active & (steps + counts >= budgets)
        return sampled, counts, done, pools, dpools

    return program
