"""Continuous-batching serving runtime over a paged KV cache.

The scan engine (``serving.engine``) compiles one decode program per
``(B, S, max_new)`` shape — ideal when requests arrive in shape-uniform
batches, hopeless for mixed-length traffic, which either pads every
request to the worst case or re-compiles per shape.  This runtime serves a
*stream* of heterogeneous requests with exactly ONE compiled decode step:

  * **Paged KV cache** — instead of a per-request contiguous
    ``(B, capacity)`` cache, KV lives in a shared pool of fixed-size pages
    (``models.layers.paged_pools_init``); each serving *slot* holds a page
    table of pool indices.  A slot's context can grow page-by-page, and
    slots of wildly different lengths share one allocation.
  * **Continuous scheduling** — a host-side scheduler admits queued
    requests into a fixed array of ``max_slots`` slots, runs one compiled
    decode step for the whole in-flight set per token, and retires
    finished slots via an in-program **done-mask**.  Admissions,
    retirements, and page-table edits change traced VALUES only (token
    ids, positions, table entries), never shapes — so the decode program
    traces exactly once per pool geometry, guarded by
    :func:`decode_trace_count` (same contract as ``serving.engine``).
  * **Prefix page reuse + suffix-only prefill** — full prompt pages are
    keyed by a chained content hash; a request whose prompt shares a
    page-aligned prefix with an in-flight request reuses those pages
    (refcount bump) and prefills ONLY the uncached suffix through
    ``models.transformer.prefill_paged`` (the cached prefix's FLOPs are
    skipped entirely — ``stats["prefill_tokens"]`` accounts for it).
  * **LRU page retention** (``retain_pages=True``) — hashed pages whose
    refcount drops to zero park on an LRU list instead of the free list
    and are evicted only under pool pressure, so a shared system prompt
    costs prefill compute once across the server's lifetime, not once
    per concurrent burst.
  * **Chunked prefill** — admission is split into ``begin_admit`` (page +
    slot reservation, no compute) and ``prefill_step`` (one fixed-size
    chunk of the prompt through the chunk program, compiled once per
    chunk length with a *traced* offset).  ``serving.driver`` interleaves
    chunks of a long prompt with decode steps of in-flight streams, which
    bounds their inter-token stalls and queued requests' TTFT.
  * **Paged attention** — the decode attend either gathers pages in jnp
    (``kernels.ref.paged_attention_ref``, the CPU default) or runs the
    fused Pallas kernel (``kernels.paged_attention``, the TPU default;
    ``use_pallas=None`` auto-detects like ``wash_shuffle``).

Per-request **parity contract** (``tests/test_batching.py``): a request
served through a busy continuous batch yields token-for-token the same
output as serving it alone through ``engine.generate_reference`` with the
same key — scheduling is a throughput optimization, not a semantics
change.

Prefill still compiles once per distinct prompt length (shape-dependent,
like the scan engine); decode — the steady-state hot path where a request
spends ``max_new - 1`` of its steps — never re-traces.

Serving modes mirror the engine: ``soup`` / ``member`` construct the
server with single-model params; ``ensemble`` holds the stacked
population, decodes every member per step against per-member pools, and
averages logits (``averaging.balanced_mean``) before sampling.

Example::

    server = ContinuousServer(params, cfg, page_size=16, max_slots=8)
    out = server.run([Request(0, prompt_a, max_new=32),
                      Request(1, prompt_b, max_new=7)])
    # out[0].tokens, out[1].tokens — each identical to serving alone
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core.compat import donate_argnums
from repro.core import averaging
from repro.models import layers as L
from repro.models import transformer as M
from repro.serving.engine import MODES, averaged_params, serving_params

PyTree = Any

#: pool page 0 is never allocated: inactive slots' page tables point here,
#: so their (masked, garbage) writes can't corrupt live pages.
SCRATCH_PAGE = 0

#: bucket edges for the per-step speculative rollback histogram (tokens
#: drafted but rejected across the in-flight set; draft_k is small, so
#: small-integer buckets resolve the whole range)
SPEC_ROLLBACK_EDGES = (0.5, 1.5, 2.5, 4.5, 8.5, 16.5, 32.5)


# ---------------------------------------------------------------------------
# trace counters + executable cache (same contract as serving.engine)
# ---------------------------------------------------------------------------

_DECODE_TRACES = [0]
_PREFILL_TRACES = [0]
_EXEC_CACHE: Dict[Tuple, Callable] = {}


def reset_trace_counts() -> None:
    _DECODE_TRACES[0] = 0
    _PREFILL_TRACES[0] = 0


def decode_trace_count() -> int:
    """Traces of the continuous decode-step program (1 per pool geometry)."""
    return _DECODE_TRACES[0]


def prefill_trace_count() -> int:
    """Traces of the prefill programs: one per distinct chunk length (the
    chunk offset ``pos0`` is traced, so chunks of one length share a
    program across slots, offsets, and cached-prefix depths)."""
    return _PREFILL_TRACES[0]


def executable_cache_size() -> int:
    return len(_EXEC_CACHE)


def clear_executable_cache() -> None:
    _EXEC_CACHE.clear()


# ---------------------------------------------------------------------------
# requests / results / slots
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request in the stream.

    ``key`` is required when the server samples (temperature > 0) — the
    same discipline as ``engine.generate`` — and must be per-request, so
    identical prompts in one stream draw independent tokens."""

    uid: Any
    tokens: np.ndarray  # (S,) int32 prompt
    max_new: int
    key: Optional[jax.Array] = None


@dataclasses.dataclass(frozen=True)
class Result:
    uid: Any
    tokens: np.ndarray  # (S + max_new,) int32: prompt + generated


@dataclasses.dataclass
class _Slot:
    uid: Any
    prompt: np.ndarray
    max_new: int
    pages: List[int]         # pool pages, prompt-order (shared and owned)
    total_pages: int         # worst-case pages this request can ever hold
    out: List[int]           # sampled tokens so far (out[-1] is pending)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def write_pos(self) -> int:
        # the pending token out[-1] has not been written yet; it lands at
        # absolute position prompt_len + (len(out) - 1) this step
        return self.prompt_len + len(self.out) - 1

    @property
    def future_pages(self) -> int:
        return self.total_pages - len(self.pages)


def _total_pages(prompt_len: int, max_new: int, page_size: int) -> int:
    # tokens ever written to the pool: S prompt + (max_new - 1) decode
    # inputs (the final sampled token is never fed back)
    stored = prompt_len + max_new - 1
    return max(-(-stored // page_size), 1)


@dataclasses.dataclass
class _Prefill:
    """An admission in progress: pages + a slot are reserved, but only
    ``pos`` of the prompt's tokens are in the pool so far.  Produced by
    ``ContinuousServer._begin_admit``; advanced (one chunk per call) by
    ``_prefill_step`` until the prompt is fully prefilled, at which point
    the first token is sampled and the slot goes live."""

    uid: Any
    prompt: np.ndarray
    max_new: int
    key: jax.Array
    pages: List[int]         # ALL prompt pages (shared prefix + owned)
    total_pages: int
    pos: int                 # tokens already in the pool
    cached_tokens: int       # prefix tokens reused (their FLOPs skipped)
    slot_index: int          # reserved decode slot
    digests: List[bytes]     # chain hashes of the prompt's full pages

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def remaining(self) -> int:
        return self.prompt_len - self.pos


# ---------------------------------------------------------------------------
# host-side page pool: free list, refcounts, prefix hash index
# ---------------------------------------------------------------------------


class _PagePool:
    """Host bookkeeping for the device page pool.

    Pages are refcounted: a page backing a shared prompt prefix is held by
    every slot that deduped onto it and freed when the last holder
    retires.  ``prefix`` maps the chained content hash of a page-aligned
    prompt chunk to the live page holding it.

    With ``retain=True``, a hashed page whose refcount drops to zero is
    *parked* on an LRU list (content + hash kept, sharable) instead of
    freed; ``alloc`` evicts the oldest parked page only once the free
    list is empty.  Every page is always in exactly one of three states —
    free, parked (LRU), or refcounted — so
    ``free_count + retained_count + len(refcount) == num_pages - 1``."""

    def __init__(self, num_pages: int, retain: bool = False):
        self.num_pages = num_pages
        self.retain = retain
        self.free: deque = deque(range(1, num_pages))  # page 0 = scratch
        self.refcount: Dict[int, int] = {}
        self.prefix: Dict[bytes, int] = {}
        self.hash_of: Dict[int, bytes] = {}
        self.lru: "OrderedDict[int, None]" = OrderedDict()  # oldest first
        self.lru_hits = 0
        self.lru_evictions = 0

    @property
    def free_count(self) -> int:
        return len(self.free)

    @property
    def retained_count(self) -> int:
        return len(self.lru)

    @property
    def available_count(self) -> int:
        """Pages an admission may claim: free + evictable (parked)."""
        return len(self.free) + len(self.lru)

    @property
    def used_count(self) -> int:
        """Pages held by live slots/prefills (parked pages are not used)."""
        return len(self.refcount)

    def alloc(self) -> int:
        if self.free:
            page = self.free.popleft()
        else:  # pool pressure: evict the least-recently-parked page
            page, _ = self.lru.popitem(last=False)
            del self.prefix[self.hash_of.pop(page)]
            self.lru_evictions += 1
        self.refcount[page] = 1
        return page

    def share(self, digest: bytes) -> Optional[int]:
        page = self.prefix.get(digest)
        if page is None:
            return None
        if page in self.lru:  # revive: parked content is still valid KV
            del self.lru[page]
            self.refcount[page] = 1
            self.lru_hits += 1
        else:
            self.refcount[page] += 1
        return page

    def register(self, page: int, digest: bytes) -> None:
        self.prefix[digest] = page
        self.hash_of[page] = digest

    def release(self, page: int) -> None:
        self.refcount[page] -= 1
        if self.refcount[page] == 0:
            del self.refcount[page]
            if self.retain and page in self.hash_of:
                self.lru[page] = None  # park, most-recently-used last
                return
            digest = self.hash_of.pop(page, None)
            if digest is not None:
                self.prefix.pop(digest, None)
            self.free.append(page)


def _chain_hashes(tokens: np.ndarray, page_size: int) -> List[bytes]:
    """Chained per-page digests of the prompt's full pages: page j's key
    covers tokens[0 : (j+1)*page_size], so equal keys mean equal *prefixes*
    (not just equal chunks) — the prefix property page sharing needs."""
    digests = []
    h = b""
    for j in range(tokens.shape[0] // page_size):
        chunk = np.ascontiguousarray(
            tokens[j * page_size:(j + 1) * page_size], dtype=np.int32
        )
        h = hashlib.sha1(h + chunk.tobytes()).digest()
        digests.append(h)
    return digests


# ---------------------------------------------------------------------------
# sampling (step index per SLOT, unlike the engine's shared scalar)
# ---------------------------------------------------------------------------


def _sample_steps(last, keys, steps, temperature, greedy: bool):
    """Next-token ids (B,) from last-position logits (B, V), under the
    device scope ``sample``.

    Same fold-in scheme as ``engine._sample`` but with a per-slot step
    vector — slots in a continuous batch sit at different depths of their
    streams, yet each stream must equal the request served alone."""
    with jax.named_scope("sample"):
        if greedy:
            return jnp.argmax(last, axis=-1).astype(jnp.int32)
        ks = jax.vmap(jax.random.fold_in)(keys, steps)
        return jax.vmap(
            lambda lg, k: jax.random.categorical(k, lg)
        )(last.astype(jnp.float32) / temperature, ks).astype(jnp.int32)


@jax.jit
def _set_slot_key(keys, slot, key):
    """The slot-key table with entry ``slot`` set to ``key``: one program
    for every slot (the index is traced)."""
    return keys.at[slot].set(key)


# ---------------------------------------------------------------------------
# compiled programs
# ---------------------------------------------------------------------------


def _pool_names(cfg: ModelConfig) -> Tuple[str, ...]:
    """The pools of ``L.paged_pools_init``'s pytree, in argument order."""
    return ("latent",) if cfg.mla else ("k", "v")


def _pool_argnums(cfg: ModelConfig, at: int,
                  sets: int = 1) -> Tuple[int, ...]:
    """The argument positions of ``sets`` pool pytrees from ``at``, one
    argument a pool (:func:`_pools_apart`)."""
    return tuple(range(at, at + sets * len(_pool_names(cfg))))


def _pools_apart(cfg: ModelConfig, fn: Callable, at: int, sets: int = 1):
    """``fn``, whose ``sets`` pool pytrees start at argument ``at``, as a
    program taking each pool as an argument of its own: ``(..., k_pool,
    v_pool, ...)`` for GQA, ``(..., latent_pool, ...)`` for MLA, ``None``
    for each pool of an absent set.  The program keeps ``fn``'s name,
    which the profiler shows."""
    names = _pool_names(cfg)
    n = len(names)

    def program(*args):
        trees = []
        for s in range(sets):
            got = args[at + s * n:at + (s + 1) * n]
            trees.append(None if got[0] is None else dict(zip(names, got)))
        return fn(*args[:at], *trees, *args[at + sets * n:])

    program.__name__ = program.__qualname__ = fn.__name__
    return program


def _jit_pools(cfg: ModelConfig, fn: Callable, at: int, sets: int = 1):
    """Jit :func:`_pools_apart` of ``fn``, its pools donated."""
    return jax.jit(_pools_apart(cfg, fn, at, sets),
                   donate_argnums=donate_argnums(_pool_argnums(cfg, at, sets)))


def _apart(cfg: ModelConfig, pools) -> Tuple:
    """A pool pytree as the programs take it, one argument a pool."""
    if pools is None:
        return (None,) * len(_pool_names(cfg))
    return tuple(pools[name] for name in _pool_names(cfg))


def _build_admit(cfg: ModelConfig, ensemble: bool, S: int, n_pages: int,
                 page_size: int, greedy: bool):
    """Prefill + page-commit + first-token sample, one jit per prompt length.

    ``write_mask`` skips pages the scheduler deduped onto a shared prefix
    (their content is already in the pool — same tokens, same params, same
    prefill program ⇒ same KV)."""

    def cont_prefill_admit(params, pools, tokens, page_ids, write_mask, key,
                           temperature):
        _PREFILL_TRACES[0] += 1
        # trace-time host effect: the compile counters mirror the
        # one-executable-per-geometry contract _*_TRACES guard
        obs.get().record_compile("cont_prefill_admit", S=S)
        batch = {"tokens": tokens}
        if ensemble:
            logits, cache = jax.vmap(
                lambda p: M.prefill(p, cfg, batch, capacity=S)
            )(params)
            k_new = cache["kv"]["k"][:, :, 0]   # (N, L, S, KV, hd)
            v_new = cache["kv"]["v"][:, :, 0]
            last = averaging.balanced_mean(logits)[:, -1]
        else:
            logits, cache = M.prefill(params, cfg, batch, capacity=S)
            k_new = cache["kv"]["k"][:, 0]      # (L, S, KV, hd)
            v_new = cache["kv"]["v"][:, 0]
            last = logits[:, -1]

        pad = n_pages * page_size - S
        def paged(a):  # (..., S, KV, hd) -> (..., n_pages, page_size*KV, hd)
            a = jnp.pad(a, [(0, 0)] * (a.ndim - 3) + [(0, pad), (0, 0), (0, 0)])
            return a.reshape(a.shape[:-3] + (n_pages, -1, a.shape[-1]))

        sel = write_mask[:, None, None]
        lead = (slice(None),) * (2 if ensemble else 1)

        def commit(pool, new):
            cur = pool[lead + (page_ids,)]
            return pool.at[lead + (page_ids,)].set(
                jnp.where(sel, paged(new), cur))

        pools = {"k": commit(pools["k"], k_new),
                 "v": commit(pools["v"], v_new)}
        token0 = _sample_steps(last, key[None], jnp.zeros((1,), jnp.int32),
                               temperature, greedy)[0]
        return pools, token0

    return _jit_pools(cfg, cont_prefill_admit, 1)


def _build_chunk(cfg: ModelConfig, ensemble: bool, greedy: bool,
                 spec: bool = False):
    """One prompt chunk through ``M.prefill_paged``: compiled once per
    chunk LENGTH — the offset ``pos0``, the page table, and the sampling
    key are all traced, so one program serves every slot, every chunk
    position, and every cached-prefix depth.

    The returned ``token0`` is the first sampled token; the host uses it
    only when the chunk completes the prompt (intermediate chunks' last
    rows are mid-prompt positions).

    ``spec`` servers run the chunk through the DRAFT model too (same
    tokens, same table) so the draft pools hold the prompt's soup-side
    K/V before the first speculative step; ``token0`` still comes from
    the verify side.  Prefix pages stay sharable: a page's content in
    BOTH pools is a pure function of (tokens, params), so a chain-hash
    hit is valid for the draft pool exactly when it is for the verify
    pool."""

    def cont_prefill_chunk(params, draft_params, pools, dpools, tokens, pos0,
                           table, key, temperature):
        _PREFILL_TRACES[0] += 1
        obs.get().record_compile("cont_prefill_chunk",
                                 T=int(tokens.shape[-1]))
        if ensemble:
            lgs, pools = jax.vmap(lambda p, pl_: M.prefill_paged(
                p, cfg, tokens, pos0, pl_, table))(params, pools)
            last = averaging.balanced_mean(lgs)[:, -1]
        else:
            lg, pools = M.prefill_paged(params, cfg, tokens, pos0, pools,
                                        table)
            last = lg[:, -1]
        if spec:
            _, dpools = M.prefill_paged(draft_params, cfg, tokens, pos0,
                                        dpools, table)
        token0 = _sample_steps(last, key[None], jnp.zeros((1,), jnp.int32),
                               temperature, greedy)[0]
        return pools, dpools, token0

    return _jit_pools(cfg, cont_prefill_chunk, 2, sets=2)


def _chunk_program(cfg: ModelConfig, ensemble: bool, T: int, max_pages: int,
                   page_size: int, num_pages: int, greedy: bool,
                   kv_dtype: Optional[str] = None, spec: bool = False):
    key = ("cont_chunk", cfg, ensemble, T, max_pages, page_size, num_pages,
           greedy, kv_dtype, spec)
    if key not in _EXEC_CACHE:
        _EXEC_CACHE[key] = _build_chunk(cfg, ensemble, greedy, spec)
    return _EXEC_CACHE[key]


def _build_decode(cfg: ModelConfig, ensemble: bool, greedy: bool,
                  use_pallas: bool):
    """THE continuous decode step: one token for the whole in-flight set.

    Every operand is traced — token ids, write positions, per-slot sample
    steps, budgets, the active mask, page tables, keys, temperature — so
    the program compiles once per pool geometry and is reused across every
    admission/retirement the stream ever makes.  It takes each pool as an
    argument of its own (:func:`_pools_apart`)."""

    def cont_decode(params, pools, tokens, positions, steps, budgets, active,
                    page_tables, keys, temperature):
        _DECODE_TRACES[0] += 1
        obs.get().record_compile("cont_decode",
                                 slots=int(tokens.shape[0]))
        if ensemble:
            lgs, pools = jax.vmap(lambda p, pl_: M.decode_step_paged(
                p, cfg, tokens, positions, pl_, page_tables, use_pallas,
            ))(params, pools)
            logits = averaging.balanced_mean(lgs)
        else:
            logits, pools = M.decode_step_paged(
                params, cfg, tokens, positions, pools, page_tables,
                use_pallas,
            )

        sampled = _sample_steps(logits[:, -1], keys, steps, temperature,
                                greedy)
        sampled = jnp.where(active, sampled, 0)
        done = active & (steps + 1 >= budgets)
        return sampled, done, pools

    return _pools_apart(cfg, cont_decode, 1)


def _build_spec_decode(cfg: ModelConfig, ensemble: bool, greedy: bool,
                       use_pallas: bool, draft_k: int):
    """The speculative decode step: draft ``k`` tokens with the soup, then
    verify all of them in ONE batched ensemble step — emitting up to
    ``k`` tokens per call, bitwise the plain path at fp32 KV.  Program
    logic lives in ``serving.speculative``; this wrapper owns the trace
    counter so the one-executable-per-(geometry, draft_k, kv_dtype)
    contract is guarded by the same ``decode_trace_count``."""
    from repro.serving import speculative

    inner = speculative.build_speculative_decode(
        cfg, ensemble, greedy, use_pallas, draft_k)

    def cont_spec_decode(*args):
        _DECODE_TRACES[0] += 1
        obs.get().record_compile("cont_spec_decode", draft_k=draft_k)
        return inner(*args)

    return _pools_apart(cfg, cont_spec_decode, 2, sets=2)


def _programs(cfg: ModelConfig, ensemble: bool, geometry: Tuple,
              greedy: bool, use_pallas: bool,
              kv_dtype: Optional[str] = None,
              draft_k: Optional[int] = None):
    """The decode program from the module executable cache — speculative
    when ``draft_k`` is set (``None`` = plain one-token decode)."""
    key = ("continuous", cfg, ensemble, geometry, greedy, use_pallas,
           kv_dtype, draft_k)
    if key not in _EXEC_CACHE:
        if draft_k is None:
            _EXEC_CACHE[key] = jax.jit(
                _build_decode(cfg, ensemble, greedy, use_pallas),
                donate_argnums=donate_argnums(_pool_argnums(cfg, 1)))
        else:
            _EXEC_CACHE[key] = jax.jit(
                _build_spec_decode(cfg, ensemble, greedy, use_pallas,
                                   draft_k),
                donate_argnums=donate_argnums(_pool_argnums(cfg, 2, 2)))
    return _EXEC_CACHE[key]


def _admit_program(cfg: ModelConfig, ensemble: bool, S: int, n_pages: int,
                   page_size: int, num_pages: int, greedy: bool):
    key = ("cont_admit", cfg, ensemble, S, n_pages, page_size, num_pages,
           greedy)
    if key not in _EXEC_CACHE:
        _EXEC_CACHE[key] = _build_admit(cfg, ensemble, S, n_pages, page_size,
                                        greedy)
    return _EXEC_CACHE[key]


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


class ContinuousServer:
    """Continuous-batching server: queue in, per-request token streams out.

    Parameters
    ----------
    params : single-model params (modes ``soup``/``member``) or the stacked
        ``(N, ...)`` population (mode ``ensemble``) — exactly the routing
        of ``engine.generate``; use :meth:`from_trained` to go straight
        from a training result.
    page_size : tokens per KV page.
    max_slots : in-flight request capacity (the decode step's batch).
    num_pages : pool size, shared by all slots (page 0 is scratch).
    max_pages_per_slot : page-table width = the longest context one slot
        can hold; defaults to the whole pool.
    temperature / use_pallas : stream-wide sampling temperature and
        attend-kernel routing (None = Pallas on TPU, jnp oracle elsewhere).
    prefill_chunk : split every prompt prefill into chunks of at most this
        many tokens (None = whole suffix in one program).  ``step()`` still
        finishes a request's prefill before decoding — chunk/decode
        INTERLEAVING is the driver's job (``serving.driver``), this knob
        only fixes the compiled chunk geometry.
    retain_pages : park refcount-0 hashed pages on an LRU list (evicted
        under pressure) instead of freeing them, so recurring prompts —
        a shared system prompt above all — skip their prefill compute on
        every later request.  Off by default: ``run()``-style one-shot
        streams expect a drained pool to be empty.
    speculative / draft_k : draft ``draft_k`` tokens per decode call with
        the population soup and verify them in one batched ensemble step
        (``serving.speculative``) — up to ``draft_k`` tokens emitted per
        call, bitwise the plain path at fp32 KV.  Requires the
        suffix-prefill path (the draft pools are prefilled by the same
        chunk programs) and a dense config.  In ``soup``/``member`` mode
        the model drafts for itself (accept rate 1.0 under greedy — the
        mechanics without the population speed-up).
    kv_dtype : ``None`` stores KV pages in the param dtype (the bitwise
        path); ``"int8"`` quantizes every pool page symmetrically with a
        per-(layer, page) float32 scale (``models.layers``), halving pool
        HBM; decode then matches fp32 to a pinned tolerance, not bitwise.
    """

    def __init__(self, params: PyTree, cfg: ModelConfig, *,
                 mode: str = "soup", temperature: float = 0.0,
                 page_size: int = 16, max_slots: int = 4,
                 num_pages: int = 64,
                 max_pages_per_slot: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 retain_pages: bool = False,
                 speculative: bool = False, draft_k: int = 4,
                 kv_dtype: Optional[str] = None):
        if mode not in MODES:
            raise ValueError(
                f"unknown serving mode {mode!r}; expected one of {MODES}")
        reason = M.paged_decode_supported(cfg)
        if reason is not None:
            raise NotImplementedError(f"continuous batching: {reason}")
        if page_size < 1 or max_slots < 1 or num_pages < 2:
            raise ValueError("need page_size >= 1, max_slots >= 1, "
                             "num_pages >= 2 (page 0 is scratch)")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1 (or None)")
        if kv_dtype not in L.KV_DTYPES:
            raise ValueError(
                f"kv_dtype={kv_dtype!r}; expected one of {L.KV_DTYPES}")
        self.cfg = cfg
        self.params = params
        self.ensemble = mode == "ensemble"
        self.temperature = float(temperature)
        self.greedy = self.temperature <= 0.0
        self.page_size = page_size
        self.max_slots = max_slots
        self.num_pages = num_pages
        self.max_pages = (max_pages_per_slot if max_pages_per_slot is not None
                          else num_pages - 1)
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = bool(use_pallas)
        self.prefill_chunk = prefill_chunk
        # suffix/chunk prefill needs bitwise-compatible paged numerics;
        # otherwise admissions fall back to the whole-prompt program with
        # write-mask dedup (no chunking, prefix pages shared but recomputed)
        self.suffix_prefill = M.paged_prefill_supported(cfg) is None
        self.kv_dtype = kv_dtype
        if kv_dtype is not None and not self.suffix_prefill:
            # the legacy whole-prompt admit writes raw rows straight into
            # the pool arrays — it has no quantization path
            raise NotImplementedError(
                f"kv_dtype={kv_dtype!r} needs the suffix-prefill path, "
                f"but {M.paged_prefill_supported(cfg)}")
        if cfg.mla and self.ensemble:
            raise NotImplementedError(
                "ensemble serving: the MLA latent pool and its kernel serve "
                "one model (modes soup and member)")
        self.speculative = bool(speculative)
        self.draft_k = int(draft_k)
        if self.speculative:
            from repro.serving import speculative as spec_mod

            if self.draft_k < 1:
                raise ValueError(f"draft_k must be >= 1, got {draft_k}")
            reason = spec_mod.speculative_supported(cfg)
            if reason is not None:
                raise NotImplementedError(f"speculative decode: {reason}")

        n_members = None
        if self.ensemble:
            n_members = jax.tree_util.tree_leaves(params)[0].shape[0]
        pools = L.paged_pools_init(cfg, num_pages, page_size, cfg.num_layers,
                                   kv_dtype=kv_dtype)
        if self.ensemble:
            pools = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n_members,) + x.shape), pools)
        #: the device pools, one pytree (``L.paged_pools_init``)
        self._pools = pools

        # the draft side: the population soup drafts for the ensemble; a
        # soup/member server drafts for itself.  Draft pools mirror the
        # verify pools' geometry under the SAME page tables.
        self._draft_params = None
        self._dpools = None
        if self.speculative:
            self._draft_params = (averaged_params(params) if self.ensemble
                                  else params)
            self._dpools = L.paged_pools_init(cfg, num_pages, page_size,
                                              cfg.num_layers,
                                              kv_dtype=kv_dtype)

        self._pool = _PagePool(num_pages, retain=retain_pages)
        self._slots: List[Optional[_Slot]] = [None] * max_slots
        self._prefills: List[_Prefill] = []   # admission order
        self._reserved_slots: set = set()
        self._queue: deque = deque()
        self._results: Dict[Any, Result] = {}
        # each slot's sample key, on the device: written when a slot is
        # installed, passed whole to every decode step (an idle slot keeps
        # whatever key it last held; the active mask ignores it)
        self._keys = jnp.broadcast_to(
            jax.random.split(jax.random.key(0), 1)[0], (max_slots,))
        self._temp = jnp.float32(max(self.temperature, 1e-6))
        geometry = (max_slots, self.max_pages, page_size, num_pages)
        self._decode = _programs(cfg, self.ensemble, geometry, self.greedy,
                                 self.use_pallas, kv_dtype,
                                 self.draft_k if self.speculative else None)
        self.stats = {"admitted": 0, "retired": 0, "cancelled": 0,
                      "decode_steps": 0, "pages_allocated": 0,
                      "pages_shared": 0, "peak_pages_in_use": 0,
                      "prefill_tokens": 0, "prefix_tokens_reused": 0,
                      "lru_hits": 0, "lru_evictions": 0,
                      "spec_drafted": 0, "spec_accepted": 0}

    # -- construction from a trained population -------------------------

    @classmethod
    def from_trained(cls, trained: Any, cfg: ModelConfig, *,
                     mode: str = "soup", member: int = 0, **kwargs):
        """Route a training result through ``engine.serving_params`` into a
        server: soup/member servers hold one model, ensemble the stack."""
        return cls(serving_params(trained, mode, member), cfg, mode=mode,
                   **kwargs)

    # -- queue API -------------------------------------------------------

    def validate(self, request: Request, pending=()) -> Request:
        """Check a request the way :meth:`submit` would — shared with the
        driver, which runs its own queue.  ``pending`` is any extra set of
        uids the caller already holds.  Returns the request with its
        prompt normalized to a flat int32 array."""
        tokens = np.asarray(request.tokens, np.int32).reshape(-1)
        if tokens.shape[0] < 1 or request.max_new < 1:
            raise ValueError("need a non-empty prompt and max_new >= 1")
        if not self.greedy and request.key is None:
            raise ValueError(
                "sampling (temperature>0) requires a per-request PRNG key, "
                "same discipline as engine.generate")
        # results are keyed by uid: two pending requests with one uid would
        # silently drop one stream's tokens.  (Reusing a uid AFTER its
        # request completed is fine — long-lived servers recycle ids, and
        # the overwrite is then a new result, not a lost one.)
        in_flight = {s.uid for s in self._slots if s is not None}
        in_flight |= {pf.uid for pf in self._prefills}
        if request.uid in in_flight or request.uid in pending or any(
                r.uid == request.uid for r in self._queue):
            raise ValueError(
                f"duplicate request uid {request.uid!r}: a request with "
                f"this uid is already queued or in flight")
        total = _total_pages(tokens.shape[0], request.max_new, self.page_size)
        if total > self.max_pages:
            raise ValueError(
                f"request {request.uid!r} needs {total} pages "
                f"(> max_pages_per_slot={self.max_pages})")
        if total > self.num_pages - 1:
            raise ValueError(
                f"request {request.uid!r} needs {total} pages "
                f"(> pool of {self.num_pages - 1} allocatable pages)")
        return dataclasses.replace(request, tokens=tokens)

    def submit(self, request: Request) -> None:
        self._queue.append(self.validate(request))

    @property
    def queue_len(self) -> int:
        return len(self._queue)

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    # -- scheduling ------------------------------------------------------

    def _reserved_pages(self) -> int:
        """Pages the in-flight slots/prefills may still demand (lazy
        growth never fails because admission reserved for everyone's
        worst case)."""
        live = sum(s.future_pages for s in self._slots if s is not None)
        live += sum(pf.total_pages - len(pf.pages) for pf in self._prefills)
        return live

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None and i not in self._reserved_slots:
                return i
        return None

    def _sync_pool_stats(self) -> None:
        self.stats["lru_hits"] = self._pool.lru_hits
        self.stats["lru_evictions"] = self._pool.lru_evictions
        self.stats["peak_pages_in_use"] = max(
            self.stats["peak_pages_in_use"], self._pool.used_count)
        tel = obs.get()
        if tel.enabled:
            reg = tel.registry
            reg.gauge("serve.pages_free").set(self._pool.free_count)
            reg.gauge("serve.pages_retained").set(self._pool.retained_count)
            reg.gauge("serve.pages_refcounted").set(self._pool.used_count)
            reg.gauge("serve.pages_peak").set(
                self.stats["peak_pages_in_use"])
            # prefix-dedup hit rate: fraction of prompt tokens served from
            # cached prefix pages instead of a prefill program (this is
            # also the suffix-prefill token savings)
            seen = (self.stats["prefill_tokens"]
                    + self.stats["prefix_tokens_reused"])
            if seen:
                reg.gauge("serve.prefix_dedup_hit_rate").set(
                    self.stats["prefix_tokens_reused"] / seen)
                reg.gauge("serve.prefix_tokens_reused").set(
                    self.stats["prefix_tokens_reused"])

    # -- chunked/suffix admission (the driver's scheduler hooks) ---------

    def _begin_admit(self, req: Request) -> Optional[_Prefill]:
        """Reserve a slot + every prompt page for ``req`` — NO compute.

        Finds the longest chain-hash-cached prefix run in the pool, bumps
        (or LRU-revives) those pages, and allocates the rest, so the
        returned :class:`_Prefill` starts at ``pos = cached_tokens`` and
        only the uncached suffix ever runs through a prefill program.
        Returns None when no slot is free or the worst-case page
        reservation does not fit.  Timed as the span ``serve.admit``."""
        with obs.get().span("serve.admit", uid=str(req.uid)) as span:
            S = int(req.tokens.shape[0])
            n_prompt = max(-(-S // self.page_size), 1)
            total = _total_pages(S, req.max_new, self.page_size)
            slot_i = self._free_slot()
            if slot_i is None:
                return None

            digests = _chain_hashes(req.tokens, self.page_size)
            cached = 0
            while (cached < len(digests)
                   and digests[cached] in self._pool.prefix):
                cached += 1
            # the suffix must keep >= 1 token: its last-position logits
            # sample the first output token (a fully cached prompt still
            # runs a 1-token chunk over the final position)
            cached = min(cached, (S - 1) // self.page_size)

            # reviving a parked prefix page consumes availability exactly
            # like an alloc (it leaves the LRU list), so it counts toward
            # need
            revived = sum(1 for j in range(cached)
                          if self._pool.prefix[digests[j]] in self._pool.lru)
            need = (n_prompt - cached) + revived + (total - n_prompt)
            if self._pool.available_count - self._reserved_pages() < need:
                return None

            pages: List[int] = []
            for j in range(cached):
                pages.append(self._pool.share(digests[j]))
                self.stats["pages_shared"] += 1
            for j in range(cached, n_prompt):
                pages.append(self._pool.alloc())
                self.stats["pages_allocated"] += 1
            # NOTE: freshly allocated full pages are NOT registered as
            # sharable yet — their content does not exist until a prefill
            # chunk writes it.  ``_prefill_step`` registers each page as its
            # chunk lands, so a concurrent admission can only dedup onto
            # written pages.
            self.stats["prefix_tokens_reused"] += cached * self.page_size
            span["cached_tokens"] = cached * self.page_size
            self._sync_pool_stats()

            key = req.key if req.key is not None else jax.random.key(0)
            pf = _Prefill(uid=req.uid, prompt=req.tokens,
                          max_new=req.max_new,
                          key=jax.random.split(key, 1)[0], pages=pages,
                          total_pages=total, pos=cached * self.page_size,
                          cached_tokens=cached * self.page_size,
                          slot_index=slot_i, digests=digests)
            self._reserved_slots.add(slot_i)
            self._prefills.append(pf)
            return pf

    def _prefill_step(self, pf: _Prefill, max_tokens: Optional[int] = None
                      ) -> bool:
        """Run ONE prompt chunk (at most ``max_tokens``; None = the whole
        remaining suffix) through the chunk program.  On the final chunk,
        samples the first token and installs the slot (or retires it for
        ``max_new == 1``).  Returns True when the prefill completed.
        Timed as the span ``serve.prefill_chunk``; the first token's
        readback as its child ``serve.prefill_wait``."""
        with obs.get().span("serve.prefill_chunk", uid=str(pf.uid)) as span:
            T = pf.remaining if max_tokens is None else min(max_tokens,
                                                            pf.remaining)
            span.update(tokens=T, pos=pf.pos)
            chunk = pf.prompt[pf.pos:pf.pos + T]
            table = np.full((self.max_pages,), SCRATCH_PAGE, np.int32)
            table[:len(pf.pages)] = pf.pages
            program = _chunk_program(self.cfg, self.ensemble, T,
                                     self.max_pages, self.page_size,
                                     self.num_pages, self.greedy,
                                     self.kv_dtype, self.speculative)
            self._pools, self._dpools, token0 = program(
                self.params, self._draft_params,
                *_apart(self.cfg, self._pools),
                *_apart(self.cfg, self._dpools),
                *jax.device_put((chunk, np.int32(pf.pos), table)),
                pf.key, self._temp,
            )
            written_before = pf.pos
            pf.pos += T
            self.stats["prefill_tokens"] += T
            # register the now-fully-written pages for prefix sharing —
            # never clobbering a digest already live on another page
            # (possible when the cached run was capped or LRU eviction broke
            # an older chain: the old page's release would tear down the new
            # entry)
            for j in range(written_before // self.page_size,
                           pf.pos // self.page_size):
                if (j < len(pf.digests)
                        and pf.digests[j] not in self._pool.prefix):
                    self._pool.register(pf.pages[j], pf.digests[j])
            if pf.remaining:
                return False

            with obs.get().span("serve.prefill_wait"):
                token0 = int(token0)
            self._prefills.remove(pf)
            self._reserved_slots.discard(pf.slot_index)
            slot = _Slot(uid=pf.uid, prompt=pf.prompt, max_new=pf.max_new,
                         pages=pf.pages, total_pages=pf.total_pages,
                         out=[token0])
            self.stats["admitted"] += 1
            if pf.max_new == 1:  # prefill-only request: retire immediately
                self._retire(slot)
            else:
                self._install(pf.slot_index, slot, pf.key)
            return True

    def _try_admit_legacy(self, req: Request) -> bool:
        """Whole-prompt admission through ``M.prefill`` + write-mask dedup
        — the fallback when ``M.paged_prefill_supported`` rejects the
        config (e.g. ``attn_impl="chunked"``, whose prefill numerics the
        paged attend cannot reproduce bitwise).  Shared prefix pages are
        skipped at WRITE time but their rows are still computed.  Timed
        as the span ``serve.admit``, the first token's readback as its
        child ``serve.prefill_wait``."""
        with obs.get().span("serve.admit", uid=str(req.uid)) as span:
            S = int(req.tokens.shape[0])
            n_prompt = max(-(-S // self.page_size), 1)
            total = _total_pages(S, req.max_new, self.page_size)
            slot_i = self._free_slot()
            if slot_i is None:
                return False

            digests = _chain_hashes(req.tokens, self.page_size)
            shared_pages = [self._pool.prefix.get(d) for d in digests]
            revived = sum(1 for p in shared_pages
                          if p is not None and p in self._pool.lru)
            new_now = n_prompt - sum(p is not None for p in shared_pages)
            need = new_now + revived + (total - n_prompt)
            if self._pool.available_count - self._reserved_pages() < need:
                return False

            pages: List[int] = []
            write_mask = np.ones((n_prompt,), bool)
            for j in range(n_prompt):
                page = (self._pool.share(digests[j]) if j < len(digests)
                        else None)
                if page is not None:
                    write_mask[j] = False
                    self.stats["pages_shared"] += 1
                else:
                    page = self._pool.alloc()
                    self.stats["pages_allocated"] += 1
                    if (j < len(digests)
                            and digests[j] not in self._pool.prefix):
                        self._pool.register(page, digests[j])
                pages.append(page)
            self._sync_pool_stats()
            self.stats["prefill_tokens"] += S
            span["cached_tokens"] = self.page_size * int(
                n_prompt - write_mask.sum())

            key = req.key if req.key is not None else jax.random.key(0)
            slot_key = jax.random.split(key, 1)[0]
            admit = _admit_program(self.cfg, self.ensemble, S, n_prompt,
                                   self.page_size, self.num_pages,
                                   self.greedy)
            self._pools, token0 = admit(
                self.params, *_apart(self.cfg, self._pools),
                *jax.device_put((req.tokens[None],
                                 np.asarray(pages, np.int32), write_mask)),
                slot_key, self._temp,
            )
            with obs.get().span("serve.prefill_wait"):
                token0 = int(token0)
            slot = _Slot(uid=req.uid, prompt=req.tokens, max_new=req.max_new,
                         pages=pages, total_pages=total, out=[token0])
            self.stats["admitted"] += 1
            if req.max_new == 1:  # prefill-only request: retire immediately
                self._retire(slot)
                return True
            self._install(slot_i, slot, slot_key)
            return True

    def _try_admit(self, req: Request) -> bool:
        """Fully admit ``req`` (prefill runs to completion within this
        call — chunk-sized programs if ``prefill_chunk`` is set, but never
        interleaved with decode; the driver interleaves)."""
        if not self.suffix_prefill:
            return self._try_admit_legacy(req)
        pf = self._begin_admit(req)
        if pf is None:
            return False
        while not self._prefill_step(pf, self.prefill_chunk):
            pass
        return True

    def cancel(self, uid: Any) -> bool:
        """Drop a request wherever it is — queued, prefilling, or decoding
        — releasing its pages and slot.  Returns False for unknown uids
        (already finished or never submitted).  No Result is produced."""
        for r in self._queue:
            if r.uid == uid:
                self._queue.remove(r)
                self.stats["cancelled"] += 1
                return True
        for pf in self._prefills:
            if pf.uid == uid:
                for page in pf.pages:
                    self._pool.release(page)
                self._prefills.remove(pf)
                self._reserved_slots.discard(pf.slot_index)
                self.stats["cancelled"] += 1
                return True
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.uid == uid:
                for page in slot.pages:
                    self._pool.release(page)
                self._slots[i] = None
                self.stats["cancelled"] += 1
                return True
        return False

    def _install(self, i: int, slot: _Slot, key: jax.Array) -> None:
        """Put ``slot`` live in slot ``i`` and its sample key in the key
        table: the table's one write per installed slot, counted as
        ``serve.slot_key_writes``."""
        self._slots[i] = slot
        self._keys = _set_slot_key(self._keys, i, key)
        tel = obs.get()
        if tel.enabled:
            tel.registry.counter("serve.slot_key_writes").inc()

    def _admit(self) -> None:
        while self._queue and self._free_slot() is not None:
            if not self._try_admit(self._queue[0]):
                break  # head-of-line blocks until pages free up
            self._queue.popleft()

    def _grow(self, slot: _Slot, extra: int = 0) -> None:
        """Lazy page growth: allocate the write page(s) just before they
        are needed (``extra`` covers a speculative step's lookahead —
        bounded by the budget, so it never exceeds the admission-time
        worst case).  Cannot fail — admission reserved that worst case.
        The caller syncs the pool statistics once all slots have grown."""
        need_pages = (slot.write_pos + extra) // self.page_size + 1
        while len(slot.pages) < need_pages:
            slot.pages.append(self._pool.alloc())
            self.stats["pages_allocated"] += 1

    def _shrink(self, slot: _Slot) -> None:
        """Roll back a speculative step's page-table cursor: release the
        trailing pages past the (possibly rolled-back) write position.
        Trailing decode pages are never chain-hash registered, so release
        really frees them — the pool's three-state partition (free /
        retained / refcounted) survives every rollback."""
        keep = slot.write_pos // self.page_size + 1
        while len(slot.pages) > keep:
            self._pool.release(slot.pages.pop())
        self._sync_pool_stats()

    def _retire(self, slot: _Slot) -> None:
        for page in slot.pages:
            self._pool.release(page)
        self.stats["retired"] += 1
        self._results[slot.uid] = Result(
            uid=slot.uid,
            tokens=np.concatenate([slot.prompt,
                                   np.asarray(slot.out, np.int32)]),
        )

    # -- the decode step -------------------------------------------------

    def step(self) -> List[Any]:
        """Admit what fits, dispatch ONE decode step for the in-flight set,
        retire whatever the done-mask finished.  Returns retired uids.

        The decode step is timed as the span ``serve.decode_step``, through
        the readback, with one child span per phase: ``decode_prepare``
        (host arrays, page growth), ``decode_dispatch`` (transfers and the
        call; its attribute ``puts`` counts the host-to-device transfers),
        ``decode_wait`` (the readback), ``decode_commit`` (token appends,
        retirement)."""
        before = set(self._results)
        self._admit()
        if self.active_slots == 0:
            return [u for u in self._results if u not in before]

        tel = obs.get()
        with tel.span("serve.decode_step", slots=self.active_slots):
            with tel.span("serve.decode_prepare"):
                *inputs, n_spec = self._decode_inputs()
            with tel.span("serve.decode_dispatch") as span:
                out, span["puts"] = self._dispatch_decode(*inputs)
            with tel.span("serve.decode_wait"):
                sampled, done, counts = (
                    None if x is None else np.asarray(x) for x in out)
            with tel.span("serve.decode_commit"):
                self._commit_decode(sampled, done, counts, n_spec)
        return [u for u in self._results if u not in before]

    def _decode_inputs(self) -> Tuple:
        """The decode step's host arrays, with each live slot's pages grown
        to hold this step's writes: ``(tokens, positions, steps, budgets,
        active, tables, n_spec)``, ``n_spec`` the proposals per slot of a
        speculative step."""
        B, Pmax = self.max_slots, self.max_pages
        tokens = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        steps = np.zeros((B,), np.int32)
        budgets = np.full((B,), np.iinfo(np.int32).max, np.int32)
        active = np.zeros((B,), bool)
        tables = np.full((B, Pmax), SCRATCH_PAGE, np.int32)
        n_spec = np.zeros((B,), np.int32)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if self.speculative:
                n_spec[i] = min(self.draft_k, slot.max_new - len(slot.out))
                n_spec[i] = max(n_spec[i], 1)
            self._grow(slot, extra=max(int(n_spec[i]) - 1, 0))
            tokens[i] = slot.out[-1]
            positions[i] = slot.write_pos
            steps[i] = len(slot.out)
            budgets[i] = slot.max_new
            active[i] = True
            tables[i, :len(slot.pages)] = slot.pages
        self._sync_pool_stats()
        return tokens, positions, steps, budgets, active, tables, n_spec

    def _dispatch_decode(self, *host) -> Tuple:
        """Send the step's host arrays ``(tokens, positions, steps,
        budgets, active, tables)`` to the device in one batched put and
        enqueue the decode program with the slot-key table and the
        temperature, both already on the device.  Returns the program's
        device outputs ``(sampled, done, counts)`` (``counts`` None unless
        speculative), with the pools swapped in, and the number of
        host-to-device puts made (1, whatever the slot count)."""
        inputs = (*jax.device_put(host), self._keys, self._temp)
        if self.speculative:
            (sampled, counts, done, self._pools,
             self._dpools) = self._decode(
                self.params, self._draft_params,
                *_apart(self.cfg, self._pools),
                *_apart(self.cfg, self._dpools), *inputs)
            return (sampled, done, counts), 1
        sampled, done, self._pools = self._decode(
            self.params, *_apart(self.cfg, self._pools), *inputs)
        return (sampled, done, None), 1

    def _commit_decode(self, sampled, done, counts, n_spec) -> None:
        """Append each live slot's sampled token(s), roll speculative page
        cursors back, and retire whatever the done-mask finished."""
        tel = obs.get()
        self.stats["decode_steps"] += 1
        if tel.enabled:
            tel.registry.counter("serve.decode_steps").inc()
            tel.registry.histogram(
                "serve.slot_occupancy", obs.RATIO_EDGES
            ).observe(self.active_slots / self.max_slots)

        drafted = accepted = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if self.speculative:
                m = int(counts[i])
                slot.out.extend(int(t) for t in sampled[i, :m])
                drafted += int(n_spec[i]) - 1
                accepted += m - 1
                if not done[i]:
                    # roll the page-table cursor back over rejected tokens
                    self._shrink(slot)
            else:
                slot.out.append(int(sampled[i]))
            if done[i]:
                self._retire(slot)
                self._slots[i] = None
        if self.speculative:
            self.stats["spec_drafted"] += drafted
            self.stats["spec_accepted"] += accepted
            if tel.enabled:
                reg = tel.registry
                reg.counter("serve.spec_drafted").inc(drafted)
                reg.counter("serve.spec_accepted").inc(accepted)
                if drafted:
                    reg.histogram(
                        "serve.spec_accept_ratio", obs.RATIO_EDGES
                    ).observe(accepted / drafted)
                reg.histogram(
                    "serve.spec_rollback", SPEC_ROLLBACK_EDGES
                ).observe(drafted - accepted)

    def run(self, requests: Optional[List[Request]] = None
            ) -> Dict[Any, Result]:
        """Submit ``requests`` (if given) and drain queue + slots to
        completion.  Returns every result produced so far, keyed by uid."""
        for req in requests or []:
            self.submit(req)
        while self._queue or self.active_slots:
            n_results = len(self._results)
            self.step()
            if (self.active_slots == 0 and self._queue
                    and len(self._results) == n_results):
                # submit() validates every request fits an empty pool, so
                # an idle server that cannot admit is a bookkeeping bug
                raise RuntimeError(
                    f"scheduler stalled with {len(self._queue)} queued "
                    f"requests and {self._pool.available_count} "
                    f"available pages")
        return dict(self._results)
