"""Continuous-batching runtime (repro/serving/batching.py).

Contracts under test:
  * a request served through a busy continuous batch is token-for-token
    identical to serving it alone via ``engine.generate_reference`` with
    the same key (greedy AND temperature) — staggered admissions and
    retirements change scheduling, never semantics;
  * the decode-step program compiles exactly ONCE for a whole mixed-length
    stream, across every admission/retirement (trace counter — same
    contract as the scan engine's per-shape guarantee, strengthened to one
    compile TOTAL); a second stream on the same server adds zero traces;
  * full prompt pages shared between in-flight requests are deduped via
    the chained prefix hash, refcounted, and freed when the last holder
    retires (pool returns to empty);
  * ensemble mode averages member logits before sampling (oracle: the
    scan engine's ensemble mode, itself parity-tested against the
    explicit vmap loop);
  * the Pallas paged-attention path (interpret on CPU) produces the same
    tokens as the jnp gather oracle path;
  * unsupported cache layouts (MLA, SSM state, sliding window, modality
    prefixes) are rejected loudly, and sampling without a per-request key
    is rejected like in ``engine.generate``;
  * the decode executable is keyed by (geometry, kv_dtype, draft_k):
    every distinct speculative draft length or KV dtype costs exactly
    one trace, and same-key servers share one executable;
  * int8 paged KV tracks the fp32 pools within the pinned logit
    tolerance (program-level), and on the pinned mixed stream emits
    fp32-identical tokens — speculative + int8 compose;
  * a decode step and a prompt chunk write only their own rows of the
    pools (int8: and rescale only the pages they touch);
  * the decode step's inputs cost no device op per slot: one batched put
    of its host arrays, and a device-resident slot-key table written once
    per installed slot and passed as it stands.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import transformer as M
from repro.serving import batching
from repro.serving import engine as serving

KEY = jax.random.key(0)

CFG = ModelConfig(num_layers=2, d_model=32, num_heads=4, num_kv_heads=2,
                  d_ff=64, vocab_size=50, dtype="float32")

# (prompt_len, max_new) pairs with staggered finishes: slots retire and
# re-admit mid-stream (max_slots below is smaller than the request count)
MIXED = [(5, 6), (9, 3), (3, 8), (12, 1), (7, 5), (4, 4)]


def _params():
    return M.init_params(KEY, CFG)


def _mixed_requests(temperature=0.0, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (S, mn) in enumerate(MIXED):
        prompt = rng.integers(0, CFG.vocab_size, size=(S,)).astype(np.int32)
        key = jax.random.key(100 + i) if temperature > 0 else None
        reqs.append(batching.Request(i, prompt, mn, key=key))
    return reqs


def _reference(params, req, temperature=0.0):
    return np.asarray(serving.generate_reference(
        params, CFG, {"tokens": jnp.asarray(req.tokens)[None]}, req.max_new,
        temperature=temperature, key=req.key,
    ))[0]


@pytest.fixture(autouse=True)
def _fresh_runtime():
    batching.reset_trace_counts()
    batching.clear_executable_cache()
    yield
    batching.clear_executable_cache()


# ---------------------------------------------------------------------------
# mixed-length stream parity + one-compile contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "temp"])
def test_mixed_stream_matches_per_request_reference(temperature):
    """Staggered admissions/retirements (3 slots, 6 requests, budgets from
    1 to 8 tokens) reproduce every request's solo output bitwise, with one
    decode compile for the whole stream."""
    params = _params()
    reqs = _mixed_requests(temperature)
    server = batching.ContinuousServer(
        params, CFG, temperature=temperature, page_size=4, max_slots=3,
        num_pages=32)
    out = server.run(reqs)
    assert set(out) == {r.uid for r in reqs}
    for r in reqs:
        np.testing.assert_array_equal(
            _reference(params, r, temperature), out[r.uid].tokens,
            err_msg=f"request {r.uid} (S={len(r.tokens)}, "
                    f"max_new={r.max_new}) diverged from solo serving")
    assert batching.decode_trace_count() == 1, (
        f"decode must compile once for the whole stream, "
        f"traced {batching.decode_trace_count()}x")
    # prefill compiles per distinct prompt length (shape-dependent)
    assert batching.prefill_trace_count() == len({s for s, _ in MIXED})
    assert server.stats["retired"] == len(reqs)


def test_second_stream_reuses_the_decode_executable():
    params = _params()
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=3, num_pages=32)
    server.run(_mixed_requests(seed=1))
    assert batching.decode_trace_count() == 1
    out = server.run(_mixed_requests(seed=2))
    assert batching.decode_trace_count() == 1, "re-traced on second stream"
    # second stream's requests are all present and still reference-exact
    for r in _mixed_requests(seed=2):
        np.testing.assert_array_equal(
            _reference(params, r), out[r.uid].tokens)


def test_single_step_admission_and_inflight_mix():
    """step() admits what fits and decodes everyone in flight; queue
    drains as slots retire (the continuous part of continuous batching)."""
    params = _params()
    reqs = _mixed_requests()
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32)
    for r in reqs:
        server.submit(r)
    assert server.queue_len == len(reqs)
    seen_active = 0
    finished = []
    for _ in range(100):
        finished += server.step()
        seen_active = max(seen_active, server.active_slots)
        if not server.queue_len and not server.active_slots:
            break
    assert sorted(finished) == [r.uid for r in reqs]
    assert seen_active == 2  # both slots actually ran concurrently


# ---------------------------------------------------------------------------
# paged pool: prefix dedup + refcounted frees
# ---------------------------------------------------------------------------


def test_prefix_pages_are_shared_and_refcount_freed():
    params = _params()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, CFG.vocab_size, size=(8,)).astype(np.int32)
    a = np.concatenate([shared, rng.integers(0, 50, size=(3,)).astype(np.int32)])
    b = np.concatenate([shared, rng.integers(0, 50, size=(5,)).astype(np.int32)])
    c = rng.integers(0, CFG.vocab_size, size=(11,)).astype(np.int32)
    reqs = [batching.Request("a", a, 5), batching.Request("b", b, 4),
            batching.Request("c", c, 3)]
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=3, num_pages=32)
    out = server.run(reqs)
    # the 8-token shared prefix is 2 full pages at page_size=4: request b
    # (admitted while a is in flight) reuses both
    assert server.stats["pages_shared"] == 2, server.stats
    # refcounted frees: the drained pool is completely empty again
    assert server._pool.used_count == 0
    assert not server._pool.refcount and not server._pool.prefix
    # sharing pages never changes tokens
    for r in reqs:
        np.testing.assert_array_equal(_reference(params, r), out[r.uid].tokens)


def test_page_pressure_queues_without_deadlock():
    """A pool too small for all requests at once still serves the stream
    (admission reserves worst-case pages; head-of-line waits for frees)."""
    params = _params()
    rng = np.random.default_rng(4)
    reqs = [batching.Request(i, rng.integers(0, 50, (9,)).astype(np.int32), 6)
            for i in range(4)]
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=4, num_pages=8)
    out = server.run(reqs)
    assert len(out) == 4
    for r in reqs:
        np.testing.assert_array_equal(_reference(params, r), out[r.uid].tokens)
    assert server.stats["peak_pages_in_use"] <= 7


def test_oversized_request_rejected_at_submit():
    params = _params()
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=8)
    big = np.zeros((40,), np.int32)
    with pytest.raises(ValueError, match="pages"):
        server.submit(batching.Request("big", big, 8))


# ---------------------------------------------------------------------------
# prefill-only retirement, same-step dedup, chunked geometry
# ---------------------------------------------------------------------------


def test_max_new_one_retires_at_prefill():
    """max_new=1: the prefill program's sampled token completes the
    request, so it must retire WITHOUT ever occupying the decode batch
    (a decode dispatch for it would read an uninitialized slot)."""
    params = _params()
    rng = np.random.default_rng(9)
    reqs = [batching.Request(i, rng.integers(0, 50, (s,)).astype(np.int32), 1)
            for i, s in enumerate([7, 4])]
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=16)
    out = server.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(_reference(params, r), out[r.uid].tokens)
    assert server.stats["decode_steps"] == 0
    assert server.stats["retired"] == 2
    assert server._pool.used_count == 0


def test_same_step_prefix_dedup():
    """Two requests sharing a prompt prefix, admitted by the SAME step()
    call: the first admission's prefill registers its page digests before
    the second admission runs, so the second must share, not recompute."""
    params = _params()
    rng = np.random.default_rng(10)
    shared = rng.integers(0, 50, (8,)).astype(np.int32)
    a = np.concatenate([shared, rng.integers(0, 50, (3,)).astype(np.int32)])
    b = np.concatenate([shared, rng.integers(0, 50, (5,)).astype(np.int32)])
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32)
    server.submit(batching.Request("a", a, 4))
    server.submit(batching.Request("b", b, 4))
    server.step()  # one step admits BOTH (two free slots)
    assert server.stats["admitted"] == 2
    assert server.stats["pages_shared"] == 2  # the 8-token prefix = 2 pages
    assert server.stats["prefix_tokens_reused"] == 8
    out = server.run()
    for uid, prompt in (("a", a), ("b", b)):
        np.testing.assert_array_equal(
            _reference(params, batching.Request(uid, prompt, 4)),
            out[uid].tokens)


def test_chunked_prefill_same_tokens_fewer_trace_shapes():
    """prefill_chunk splits every admission into fixed-size chunk
    programs: tokens stay bitwise identical and the compiled prefill
    shapes collapse to {chunk, remainders} instead of one per prompt
    length."""
    params = _params()
    reqs = _mixed_requests(seed=11)
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=3, num_pages=32,
                                       prefill_chunk=4)
    out = server.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(_reference(params, r), out[r.uid].tokens)
    assert batching.decode_trace_count() == 1
    # chunk lengths are min(4, remaining): {4} plus short remainders —
    # never more shapes than the chunk size
    assert batching.prefill_trace_count() <= 4


# ---------------------------------------------------------------------------
# LRU retention: revival, eviction under pressure, stall recovery
# ---------------------------------------------------------------------------


def test_lru_retention_revives_prefix_pages():
    """retain_pages: a drained request's hashed pages park on the LRU
    list; resubmitting the same prompt revives them and prefills ONLY
    the uncached suffix (token accounting by the server's counters)."""
    params = _params()
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, 50, (11,)).astype(np.int32)
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32,
                                       retain_pages=True)
    out1 = server.run([batching.Request("r1", prompt, 4)])
    assert server._pool.retained_count > 0
    assert not server._pool.refcount
    before = dict(server.stats)
    out2 = server.run([batching.Request("r2", prompt, 4)])
    np.testing.assert_array_equal(out1["r1"].tokens, out2["r2"].tokens)
    assert server.stats["lru_hits"] > 0
    # 11 tokens at page_size=4: 2 full prompt pages (8 tokens) are
    # cacheable; the resubmission prefills only the 3-token suffix
    assert server.stats["prefix_tokens_reused"] - before["prefix_tokens_reused"] == 8
    assert server.stats["prefill_tokens"] - before["prefill_tokens"] == 3
    # three-state invariant: every page is free, parked, or referenced
    pool = server._pool
    assert (pool.free_count + pool.retained_count + len(pool.refcount)
            == server.num_pages - 1)


def test_lru_eviction_recovers_from_full_parked_pool():
    """A pool whose idle pages are all parked must evict LRU-first to
    admit fresh prompts — retention never causes an admission stall."""
    params = _params()
    rng = np.random.default_rng(13)
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=8,
                                       retain_pages=True)
    for i in range(4):  # distinct prompts, enough to cycle the tiny pool
        prompt = rng.integers(0, 50, (9,)).astype(np.int32)
        out = server.run([batching.Request(i, prompt, 3)])
        np.testing.assert_array_equal(
            _reference(params, batching.Request(i, prompt, 3)),
            out[i].tokens)
    assert server.stats["lru_evictions"] > 0
    pool = server._pool
    assert (pool.free_count + pool.retained_count + len(pool.refcount)
            == server.num_pages - 1)
    assert not pool.refcount


def test_cancel_releases_pages_at_every_stage():
    """cancel() drops a request whether queued or decoding; its pages
    return to the pool and the stream's other requests are unaffected."""
    params = _params()
    rng = np.random.default_rng(14)
    keep = batching.Request("keep", rng.integers(0, 50, (6,)).astype(np.int32), 5)
    dec = batching.Request("dec", rng.integers(0, 50, (9,)).astype(np.int32), 8)
    queued = batching.Request("q", rng.integers(0, 50, (5,)).astype(np.int32), 4)
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32)
    server.submit(keep)
    server.submit(dec)
    server.submit(queued)
    server.step()  # admits keep + dec (2 slots); q stays queued
    assert server.cancel("q") and server.cancel("dec")
    assert not server.cancel("nope")
    out = server.run()
    assert set(out) == {"keep"}
    np.testing.assert_array_equal(_reference(params, keep), out["keep"].tokens)
    assert server.stats["cancelled"] == 2
    assert server._pool.used_count == 0


# ---------------------------------------------------------------------------
# modes + kernel routing
# ---------------------------------------------------------------------------


def test_ensemble_mode_matches_scan_engine():
    popn = jax.vmap(lambda k: M.init_params(k, CFG))(jax.random.split(KEY, 3))
    reqs = _mixed_requests(seed=5)[:4]
    server = batching.ContinuousServer.from_trained(
        popn, CFG, mode="ensemble", page_size=4, max_slots=2, num_pages=32)
    out = server.run(reqs)
    for r in reqs:
        expect = np.asarray(serving.generate(
            popn, CFG, {"tokens": jnp.asarray(r.tokens)[None]}, r.max_new,
            mode="ensemble"))[0]
        np.testing.assert_array_equal(expect, out[r.uid].tokens)
    assert batching.decode_trace_count() == 1


def test_member_mode_routes_params():
    from repro.core import population as pop

    popn = jax.vmap(lambda k: M.init_params(k, CFG))(jax.random.split(KEY, 3))
    req = _mixed_requests(seed=6)[0]
    server = batching.ContinuousServer.from_trained(
        popn, CFG, mode="member", member=1, page_size=4, max_slots=2,
        num_pages=32)
    out = server.run([req])
    direct = _reference(pop.member(popn, 1), req)
    np.testing.assert_array_equal(direct, out[req.uid].tokens)


def test_pallas_kernel_path_matches_reference_tokens():
    """use_pallas=True routes the attend through the fused kernel
    (interpret mode here) — same tokens as the jnp oracle path."""
    params = _params()
    reqs = _mixed_requests(seed=7)[:3]
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32,
                                       use_pallas=True)
    out = server.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(_reference(params, r), out[r.uid].tokens)


# ---------------------------------------------------------------------------
# speculative decode + quantized KV: executable-cache keys and tolerance
# ---------------------------------------------------------------------------


def test_one_decode_program_per_geometry_draft_k_kv_dtype():
    """The decode executable is keyed by (geometry, ..., kv_dtype,
    draft_k): same-key servers share one trace; changing draft_k or
    kv_dtype adds EXACTLY one."""
    params = _params()

    def serve(**kw):
        server = batching.ContinuousServer(params, CFG, page_size=4,
                                           max_slots=3, num_pages=32, **kw)
        server.run(_mixed_requests(seed=21))

    serve(speculative=True, draft_k=3)
    assert batching.decode_trace_count() == 1
    serve(speculative=True, draft_k=3)             # same key: pure reuse
    assert batching.decode_trace_count() == 1
    serve(speculative=True, draft_k=5)             # new draft_k: one more
    assert batching.decode_trace_count() == 2
    serve()                                        # plain (draft_k=None)
    assert batching.decode_trace_count() == 3
    serve(kv_dtype="int8")                         # plain int8
    assert batching.decode_trace_count() == 4
    serve(speculative=True, draft_k=3, kv_dtype="int8")
    assert batching.decode_trace_count() == 5, (
        "every distinct (draft_k, kv_dtype) must cost exactly one trace")


def test_int8_decode_logits_track_fp32_within_tolerance():
    """The quantized-KV numeric contract at program level: prefill a
    prompt into fp32 and int8 pools, run one paged decode step against
    each, and the logits agree within the pinned tolerance (per-element
    KV error is at most half a quantization step)."""
    from repro.models import layers as L

    params = _params()
    rng = np.random.default_rng(30)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (10,)), jnp.int32)
    table = jnp.arange(1, 6, dtype=jnp.int32)          # pages 1..5
    outs = {}
    for kv_dtype in (None, "int8"):
        pools = L.paged_pools_init(CFG, num_pages=8, page_size=4,
                                   num_layers=CFG.num_layers,
                                   kv_dtype=kv_dtype)
        lg, pools = M.prefill_paged(params, CFG, prompt, 0, pools, table)
        step_logits, _ = M.decode_step_paged(
            params, CFG, jnp.argmax(lg[0, -1])[None].astype(jnp.int32),
            jnp.array([10], jnp.int32), pools, table[None])
        outs[kv_dtype] = (np.asarray(lg), np.asarray(step_logits))
    for a, b in zip(outs[None], outs["int8"]):
        np.testing.assert_allclose(a, b, rtol=0.0, atol=0.1)
    assert np.argmax(outs[None][1]) == np.argmax(outs["int8"][1])


def _filled_pools(kv_dtype, num_pages, page_size, seed):
    """Pools of the serving layout holding random bits everywhere, so a
    row a step should not touch is told apart from one it rewrote."""
    from repro.models import layers as L

    pools = L.paged_pools_init(CFG, num_pages, page_size, CFG.num_layers,
                               kv_dtype=kv_dtype)
    rng = np.random.default_rng(seed)
    if kv_dtype is None:
        return {n: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
                for n, a in pools.items()}
    out = {}
    for n, a in pools.items():
        q = rng.integers(-127, 128, a["q"].shape).astype(np.int8)
        scale = rng.uniform(1e-3, 1e-2, a["scale"].shape).astype(np.float32)
        scale[:, 0] = L.KV_SCRATCH_SCALE
        out[n] = {"q": jnp.asarray(q), "scale": jnp.asarray(scale)}
    return out


def _assert_only_rows_written(before, after, pages, offsets, kv_dtype):
    """Every pool row but ``[l, pages[i], offsets[i] * KV + h]`` (all
    layers l and kv heads h) is bitwise unchanged; an int8 pool may also
    rescale the touched pages and their scales, and nothing else."""
    KV = CFG.num_kv_heads
    for name in ("k", "v"):
        b, a = before[name], after[name]
        if kv_dtype is not None:
            keep = np.ones(b["scale"].shape, bool)
            keep[:, pages] = False
            np.testing.assert_array_equal(np.asarray(a["scale"])[keep],
                                          np.asarray(b["scale"])[keep])
            b, a = b["q"], a["q"]
        b, a = np.asarray(b), np.asarray(a)
        assert a.shape == b.shape
        keep = np.ones(b.shape[:3], bool)
        if kv_dtype is None:
            for page, off in zip(pages, offsets):
                keep[:, page, off * KV:(off + 1) * KV] = False
        else:
            keep[:, pages] = False
        np.testing.assert_array_equal(a[keep], b[keep])
        rows = a[:, pages] != b[:, pages]
        assert rows.any(), f"{name}: the step wrote none of its rows"


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_decode_step_writes_only_its_rows(kv_dtype):
    """One paged decode step writes each slot's new K/V row into the
    whole pool in place and leaves every other row's bits alone."""
    params = _params()
    ps, num_pages = 4, 12
    before = _filled_pools(kv_dtype, num_pages, ps, seed=40)
    tables = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    positions = jnp.array([0, 6, 11], jnp.int32)
    tokens = jnp.array([3, 14, 15], jnp.int32)
    _, after = M.decode_step_paged(params, CFG, tokens, positions, before,
                                   tables)
    pos = np.asarray(positions)
    pages = np.asarray(tables)[np.arange(3), pos // ps]
    _assert_only_rows_written(before, after, pages, pos % ps, kv_dtype)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_prompt_chunk_writes_only_its_rows(kv_dtype):
    """One prompt chunk writes its T rows into the slot's pages in place
    and leaves every other row's bits alone, the cached context below
    ``pos0`` included."""
    params = _params()
    ps, num_pages, pos0, T = 4, 12, 6, 5
    before = _filled_pools(kv_dtype, num_pages, ps, seed=41)
    table = jnp.array([3, 7, 2, 9], jnp.int32)
    tokens = jnp.arange(10, 10 + T, dtype=jnp.int32)
    _, after = M.prefill_paged(params, CFG, tokens, pos0, before, table)
    pos = pos0 + np.arange(T)
    pages = np.asarray(table)[pos // ps]
    _assert_only_rows_written(before, after, pages, pos % ps, kv_dtype)


def test_int8_kv_stream_matches_fp32_tokens_on_pinned_stream():
    """End-to-end int8 serving on the pinned mixed stream: this tiny
    config's logit margins dominate the bounded KV quantization error, so
    the emitted tokens match fp32 exactly (a logit-level tolerance is the
    contract — the program-level test above — but pinning the stream
    catches any silent blow-up in quant error), and the runtime
    invariants (one trace, drained pool) hold untouched."""
    params = _params()
    fp = batching.ContinuousServer(params, CFG, page_size=4, max_slots=3,
                                   num_pages=32)
    out_fp = fp.run(_mixed_requests(seed=0))
    batching.reset_trace_counts()
    q = batching.ContinuousServer(params, CFG, page_size=4, max_slots=3,
                                  num_pages=32, kv_dtype="int8")
    out_q = q.run(_mixed_requests(seed=0))
    assert set(out_q) == set(out_fp)
    for uid in out_fp:
        np.testing.assert_array_equal(out_fp[uid].tokens, out_q[uid].tokens)
    assert batching.decode_trace_count() == 1
    assert q._pool.used_count == 0
    assert q.stats["retired"] == len(MIXED)


def test_speculative_int8_composes_and_stays_within_stream_tolerance():
    """Speculative + int8 together: the bitwise claim relaxes (a page's
    scale couples every row written to it), but the stream still serves
    completely, rolls back cleanly, and matches the plain int8 server on
    this pinned stream."""
    params = _params()
    plain = batching.ContinuousServer(params, CFG, page_size=4, max_slots=3,
                                      num_pages=32, kv_dtype="int8")
    out_plain = plain.run(_mixed_requests(seed=0))
    spec = batching.ContinuousServer(params, CFG, page_size=4, max_slots=3,
                                     num_pages=32, kv_dtype="int8",
                                     speculative=True, draft_k=4)
    out_spec = spec.run(_mixed_requests(seed=0))
    assert set(out_spec) == set(out_plain)
    for uid in out_plain:
        np.testing.assert_array_equal(out_plain[uid].tokens,
                                      out_spec[uid].tokens)
    assert spec._pool.used_count == 0
    assert spec.stats["spec_drafted"] >= spec.stats["spec_accepted"] >= 0


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------


def test_unsupported_cache_layouts_rejected():
    mla = ModelConfig(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=50, mla=True, kv_lora_rank=16,
                      qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
                      dtype="float32")
    # MLA is served from a latent pool, by one model only
    members = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]),
                                     M.init_params(KEY, mla))
    with pytest.raises(NotImplementedError, match="MLA"):
        batching.ContinuousServer(members, mla, mode="ensemble")
    swa = ModelConfig(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=50, window=8, dtype="float32")
    with pytest.raises(NotImplementedError, match="window"):
        batching.ContinuousServer(M.init_params(KEY, swa), swa)
    vlm = ModelConfig(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=50, frontend="vision",
                      num_patches=3, dtype="float32")
    with pytest.raises(NotImplementedError, match="frontend"):
        batching.ContinuousServer(M.init_params(KEY, vlm), vlm)


def test_duplicate_pending_uid_rejected_but_reuse_after_completion_ok():
    """Two pending requests with one uid would silently drop a stream
    (results are keyed by uid); reuse after completion is legitimate."""
    params = _params()
    server = batching.ContinuousServer(params, CFG, page_size=4,
                                       max_slots=2, num_pages=32)
    req = _mixed_requests(seed=8)[0]
    server.submit(req)
    with pytest.raises(ValueError, match="duplicate request uid"):
        server.submit(req)
    server.run()
    # completed: same uid admits again and produces the same tokens
    out = server.run([req])
    np.testing.assert_array_equal(_reference(params, req), out[req.uid].tokens)


def test_sampling_requires_per_request_key():
    server = batching.ContinuousServer(_params(), CFG, temperature=0.7,
                                       page_size=4, max_slots=2,
                                       num_pages=16)
    with pytest.raises(ValueError, match="per-request PRNG key"):
        server.submit(batching.Request(0, np.zeros((4,), np.int32), 2))


# ---------------------------------------------------------------------------
# decode-step inputs: one batched put, a device-resident slot-key table
# ---------------------------------------------------------------------------


@pytest.fixture
def telemetry():
    tel = obs.configure(memory=True)
    yield tel
    obs.reset()


def _binds_in_next_dispatch(server):
    """Step ``server`` once; returns the names of the primitives bound
    while its decode step was dispatched."""
    from jax._src import core

    names = []
    bind = core.Primitive.bind
    dispatch = server._dispatch_decode

    def counted(self, *args, **params):
        names.append(self.name)
        return bind(self, *args, **params)

    def spy(*host):
        core.Primitive.bind = counted
        try:
            return dispatch(*host)
        finally:
            core.Primitive.bind = bind

    server._dispatch_decode = spy
    try:
        server.step()
    finally:
        del server._dispatch_decode
    return names


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "spec"])
def test_decode_dispatch_work_does_not_grow_with_slots(speculative,
                                                       telemetry):
    """The dispatch binds the same primitives with 2 and with 8 live slots
    (no per-slot ``stack`` of keys) and makes one host-to-device put."""
    rng = np.random.default_rng(31)
    reqs = [batching.Request(i, rng.integers(0, 50, (5,)).astype(np.int32),
                             24) for i in range(8)]
    server = batching.ContinuousServer(
        _params(), CFG, page_size=4, max_slots=8, num_pages=96,
        speculative=speculative, draft_k=2)
    for r in reqs[:2]:
        server.submit(r)
    server.step()
    server.step()  # the decode program is built: measure warm dispatches
    few = _binds_in_next_dispatch(server)
    assert server.active_slots == 2
    for r in reqs[2:]:
        server.submit(r)
    many = _binds_in_next_dispatch(server)
    assert server.active_slots == 8
    assert few == many, (few, many)
    assert not {"concatenate", "expand_dims", "broadcast_in_dim"} & set(
        many), many
    spans = telemetry._sinks[-1].named("serve.decode_dispatch")
    assert len(spans) == 4 and all(s["puts"] == 1 for s in spans)


@pytest.mark.parametrize("attn_impl", ["naive", "chunked"],
                         ids=["suffix", "legacy"])
def test_slot_key_table_written_once_per_installed_slot(attn_impl,
                                                        telemetry):
    """Decode steps with no admission between them pass the same key-table
    array; each installed slot writes it once (a prefill-only request,
    retired at admission, writes nothing)."""
    cfg = dataclasses.replace(CFG, attn_impl=attn_impl)
    server = batching.ContinuousServer(
        M.init_params(KEY, cfg), cfg, temperature=0.8, page_size=4,
        max_slots=3, num_pages=32)
    assert server.suffix_prefill == (attn_impl == "naive")
    passed = []
    decode = server._decode

    def spy(*args):
        passed.append((args[-2], server.stats["admitted"]))
        return decode(*args)

    server._decode = spy
    reqs = _mixed_requests(temperature=0.8)
    server.run(reqs)
    same = [a is b for (a, n), (b, m) in zip(passed, passed[1:]) if n == m]
    assert same and all(same)
    assert any(a is not b for (a, _), (b, _) in zip(passed, passed[1:]))
    installed = sum(r.max_new > 1 for r in reqs)
    assert server.stats["admitted"] == len(reqs)
    assert telemetry.registry.counter(
        "serve.slot_key_writes").value == installed


def test_sampled_slot_reused_after_cancel_samples_with_its_own_key():
    """A request admitted into the slot a cancelled request held samples
    with its own key: both sampled streams equal their solo references."""
    params = _params()
    rng = np.random.default_rng(23)

    def request(uid, S, max_new, seed):
        return batching.Request(
            uid, rng.integers(0, 50, (S,)).astype(np.int32), max_new,
            key=jax.random.key(seed))

    a, b, c = request("a", 6, 7, 500), request("b", 9, 8, 501), \
        request("c", 5, 6, 502)
    server = batching.ContinuousServer(params, CFG, temperature=0.8,
                                       page_size=4, max_slots=2, num_pages=32)
    server.submit(a)
    server.submit(b)
    server.step()
    server.step()
    freed = [s.uid for s in server._slots].index("b")
    assert server.cancel("b")
    server.submit(c)
    server.step()
    assert server._slots[freed].uid == "c"
    out = server.run()
    assert set(out) == {"a", "c"}
    for r in (a, c):
        np.testing.assert_array_equal(_reference(params, r, 0.8),
                                      out[r.uid].tokens)
