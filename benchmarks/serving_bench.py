"""Serving engine benchmark: per-token loop vs fused scan, soup vs ensemble,
static batches vs continuous batching on mixed-length traffic.

Rows (CSV via benchmarks/run.py, mirrored into
``benchmarks/out/serving_bench.json``):

  serve_oldloop_*    the legacy per-token Python loop (fresh jit closure
                     per request + one host dispatch per token) — the bug
                     the engine replaced; its re-trace count per request
                     is reported in the derived column.
  serve_scan_*       the fused engine: one compiled decode program per
                     shape, reused across requests (0 traces after warm).
  serve_member       mode=member (single unaveraged member).
  serve_ensemble     mode=ensemble — all N members decoded per step,
                     logits averaged in-scan: the paper's accuracy
                     ceiling, priced here in tokens/sec against the soup.
  serve_static_mixed      a MIXED-length request stream served by the scan
                          engine: requests bucketed by exact (S, max_new)
                          shape, one compile per bucket — the per-shape
                          compiles ARE the cost of static batching under
                          mixed traffic, so they are timed, not excluded.
  serve_continuous_mixed  the same stream through the continuous-batching
                          paged-KV runtime: one decode compile total
                          (asserted), per-prompt-length prefill compiles,
                          admissions/retirements never retrace.
  serve_driver_whole      an SLO workload (two long prompts arriving just
                          ahead of a burst of short ones) through the
                          async request driver with whole-prompt prefill:
                          a long admission blocks the queue, so the short
                          requests' tail TTFT absorbs both long prefills.
  serve_driver_chunked    the same workload with chunked prefill
                          (interleaved round-robin with decode): short
                          requests slip between a long prompt's chunks,
                          so their tail TTFT is bounded by one chunk, not
                          one prompt.  Derived columns report p50/p99
                          TTFT over all requests AND over the shorts
                          alone — the latter is the SLO number chunking
                          exists to fix.
  serve_ensemble_paged    ensemble mode through the continuous runtime:
                          every emitted token pays one vmapped N-member
                          decode step — the baseline the speculative row
                          races.
  serve_speculative       population-powered speculative decode: the
                          soup drafts ``draft_k`` tokens, the ensemble
                          verifies all of them in ONE batched step.  The
                          bench population stacks ONE member N times —
                          the limit case of WASH's members sharing a
                          basin — so the accept rate is deterministically
                          1.0 and the row isolates the mechanism: k
                          tokens per ensemble dispatch instead of one.
                          Real WASH populations sit below that ceiling;
                          the accept-rate column is the number to watch.
  serve_quantized_kv      the soup continuous server with int8 paged KV
                          (per-page symmetric scales): tokens/sec plus
                          the capacity ledger — pages per GB vs fp32 at
                          fixed HBM, measured from the live pools' actual
                          nbytes, not a formula.

Steady-state rows (oldloop/scan/member/ensemble) exclude compile; the two
mixed-stream rows are cold on purpose; the driver rows are warmed (their
compiles are shared executables, not per-request work) so the TTFT
percentiles measure scheduling, not tracing.  Trace counts are measured
by the engines' counters, not inferred.  ``--smoke`` runs the CI
fast-lane guard: tiny config, assert the scan path compiled decode
exactly once, the continuous runtime compiled decode exactly once for
the whole stream, continuous beat static on the mixed stream, chunked
beat whole-prompt on the shorts' tail TTFT, a resubmitted prompt's
suffix-only prefill skipped its LRU-cached prefix pages (FLOP accounting
by the server's own token counters), speculative decode accepted every
draft AND out-threw the plain paged ensemble, and int8 KV fit >3x the
fp32 page count at fixed HBM — then still emits the JSON.
"""

from __future__ import annotations

import json
import os

import jax

from benchmarks._util import Row, fmt, time_fn, with_provenance

KEY = jax.random.key(0)

JSON_OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "out", "serving_bench.json")


def _problem(batch: int, prompt: int):
    from repro.configs.base import ModelConfig
    from repro.models import transformer as M

    cfg = ModelConfig(name="serve-bench", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                      dtype="float32")
    popn = jax.vmap(lambda k: M.init_params(k, cfg))(jax.random.split(KEY, 4))
    tokens = jax.random.randint(jax.random.fold_in(KEY, 1), (batch, prompt),
                                0, cfg.vocab_size)
    return cfg, popn, {"tokens": tokens}


def _mixed_stream(cfg, n_requests: int, max_prompt: int, max_new: int,
                  seed: int = 0):
    """Mixed-length traffic with some shared prompt prefixes (so the
    prefix-page dedup path is exercised, not just measured at zero).
    The generator lives in ``repro.launch.serve`` — one traffic shape for
    the CLI and the bench."""
    from repro.launch.serve import mixed_stream

    return mixed_stream(cfg, n_requests, max_prompt, max_new, seed,
                        share_prefix_every=4)


def _run_mixed(cfg, soup, reqs, page_size: int, max_slots: int):
    """(static_seconds, static_traces, continuous_seconds, server) — both
    runtimes serve the stream cold (compiles included: under mixed traffic
    the static engine's per-shape compiles are the point)."""
    import time as _time
    from collections import defaultdict

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import batching
    from repro.serving import engine as serving

    # --- static: bucket by exact shape, one scan-engine call per bucket
    serving.reset_trace_counts()
    serving.clear_executable_cache()
    buckets = defaultdict(list)
    for r in reqs:
        buckets[(len(r.tokens), r.max_new)].append(r)
    t0 = _time.perf_counter()
    for (S, mn), group in buckets.items():
        toks = jnp.asarray(np.stack([r.tokens for r in group]))
        jax.block_until_ready(
            serving.generate(soup, cfg, {"tokens": toks}, mn))
    static_s = _time.perf_counter() - t0
    static_traces = serving.decode_trace_count()

    # --- continuous: one server, one decode compile for the whole stream
    max_pages = max(
        -(-(len(r.tokens) + r.max_new) // page_size) for r in reqs)
    server = batching.ContinuousServer(
        soup, cfg, page_size=page_size, max_slots=max_slots,
        num_pages=max_slots * max_pages + 8, max_pages_per_slot=max_pages)
    batching.reset_trace_counts()
    t0 = _time.perf_counter()
    out = server.run(reqs)
    cont_s = _time.perf_counter() - t0
    assert len(out) == len(reqs)
    return static_s, static_traces, cont_s, server


def _driver_workload(cfg, quick: bool = True):
    """The SLO stress shape: two LONG prompts arrive first, then a burst
    of short ones right behind them.  Whole-prompt admission makes every
    short wait out both long prefills; chunked admission lets them
    interleave.  Fresh Request objects every call (runs mutate nothing,
    but sharing uids across servers would make the metrics lie)."""
    import numpy as np

    from repro.serving import batching

    rng = np.random.default_rng(7)
    L, S, n_short, max_new = (96, 12, 6, 8) if quick else (256, 24, 12, 16)
    reqs = [batching.Request(f"long{i}",
                             rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32),
                             max_new)
            for i in range(2)]
    reqs += [batching.Request(f"short{i}",
                              rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32),
                              max_new)
             for i in range(n_short)]
    return reqs


def _run_driver(cfg, soup, chunk, quick: bool = True, page_size: int = 8):
    """(summary, short_summary, server, seconds) for one driver variant.
    One warm pass populates the shared executable cache; the timed pass
    uses a fresh server so its stats and the TTFT percentiles are clean."""
    import time as _time

    from repro.serving import batching
    from repro.serving.driver import RequestDriver, summarize

    def serve(reqs):
        pages = sum(-(-(len(r.tokens) + r.max_new) // page_size)
                    for r in reqs)
        server = batching.ContinuousServer(
            soup, cfg, page_size=page_size, max_slots=len(reqs),
            num_pages=pages + 8, retain_pages=True)
        driver = RequestDriver(server, prefill_chunk=chunk)
        t0 = _time.perf_counter()
        metrics = driver.run(reqs)
        return metrics, server, _time.perf_counter() - t0

    serve(_driver_workload(cfg, quick))                      # warm compiles
    batching.reset_trace_counts()
    metrics, server, dt = serve(_driver_workload(cfg, quick))
    shorts = {uid: m for uid, m in metrics.items()
              if str(uid).startswith("short")}
    return summarize(metrics), summarize(shorts), server, dt


def _spec_workload(cfg, quick: bool = True):
    """Decode-heavy traffic for the ensemble-vs-speculative race: short
    prompts, long generations — the regime speculation targets (a
    prefill-bound stream pays the same prefill either way and would just
    dilute the decode-side difference being measured)."""
    import numpy as np

    from repro.serving import batching

    rng = np.random.default_rng(11)
    n, S, max_new = (8, 12, 24) if quick else (16, 24, 64)
    return [batching.Request(f"spec{i}",
                             rng.integers(0, cfg.vocab_size, (S,)).astype(np.int32),
                             max_new)
            for i in range(n)]


def _run_population(cfg, stacked, reqs_fn, speculative: bool,
                    draft_k: int = 4, page_size: int = 8):
    """(summary, server, seconds) for an ensemble-mode continuous server —
    plain or speculative — timed warm through the async driver so the
    tok/s and TTFT numbers measure decode scheduling, not tracing.
    ``max_pages_per_slot`` is sized to the workload: the paged attend
    gathers every table column, so a sloppy width taxes the verify
    step's B·k rows fourfold."""
    import time as _time

    from repro.serving import batching
    from repro.serving.driver import RequestDriver, summarize

    def serve():
        reqs = reqs_fn()
        per_slot = max(-(-(len(r.tokens) + r.max_new) // page_size)
                       for r in reqs)
        server = batching.ContinuousServer(
            stacked, cfg, mode="ensemble", page_size=page_size,
            max_slots=len(reqs), num_pages=len(reqs) * per_slot + 8,
            max_pages_per_slot=per_slot,
            speculative=speculative, draft_k=draft_k)
        driver = RequestDriver(server)
        t0 = _time.perf_counter()
        metrics = driver.run(reqs)
        return summarize(metrics), server, _time.perf_counter() - t0

    serve()                                              # warm compiles
    return serve()


def _pool_bytes(server) -> int:
    """Live nbytes of the server's verify KV pools (int8 pools are dicts
    holding the quantized pages plus their per-page f32 scales — the
    scales are part of the footprint and are counted)."""
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(
                   server._pools))


def run(quick: bool = True):
    from repro.serving import engine as serving

    batch, prompt = (4, 16) if quick else (16, 64)
    max_new = 16 if quick else 64
    iters = 3 if quick else 5
    cfg, popn, req = _problem(batch, prompt)
    soup = serving.averaged_params(popn)
    toks = batch * max_new

    rows: list[Row] = []
    results = {}

    def add(name, us, derived):
        rows.append((name, us, fmt(derived)))
        results[name] = {"us_per_call": us, **derived}

    # --- legacy per-token loop (the replaced path) ------------------------
    serving.reset_trace_counts()
    us = time_fn(
        lambda: serving.generate_reference(soup, cfg, req, max_new),
        iters=iters, warmup=1,
    )
    calls = iters + 1
    old_traces = serving.reference_trace_count() / calls
    old_toks = toks / (us * 1e-6)
    add("serve_oldloop_soup", us,
        {"tok_s": old_toks, "traces_per_request": old_traces,
         "dispatches_per_request": max_new - 1})

    # --- fused scan engine ------------------------------------------------
    serving.reset_trace_counts()
    serving.clear_executable_cache()
    us = time_fn(
        lambda: serving.generate(soup, cfg, req, max_new),
        iters=iters, warmup=1,
    )
    scan_traces = serving.decode_trace_count()  # total, across ALL requests
    scan_toks = toks / (us * 1e-6)
    add("serve_scan_soup", us,
        {"tok_s": scan_toks, "traces_total": scan_traces,
         "dispatches_per_request": 1, "speedup_vs_oldloop": scan_toks / old_toks})

    # params are resolved once per mode (deployment-time work) so the rows
    # time the decode engine, not per-request soup/member routing
    member = serving.serving_params(popn, "member", 0)
    us = time_fn(
        lambda: serving.generate(member, cfg, req, max_new),
        iters=iters, warmup=1,
    )
    add("serve_member", us, {"tok_s": toks / (us * 1e-6)})

    stacked = serving.serving_params(popn, "ensemble")
    us = time_fn(
        lambda: serving.generate(stacked, cfg, req, max_new, mode="ensemble"),
        iters=iters, warmup=1,
    )
    ens_toks = toks / (us * 1e-6)
    add("serve_ensemble", us,
        {"tok_s": ens_toks, "members": 4,
         "soup_speedup_vs_ensemble": scan_toks / ens_toks})

    # --- static batches vs continuous batching, mixed-length stream -------
    from repro.serving import batching

    n_req = 8 if quick else 24
    reqs = _mixed_stream(cfg, n_req, max_prompt=prompt, max_new=max_new)
    static_s, static_traces, cont_s, server = _run_mixed(
        cfg, soup, reqs, page_size=4 if quick else 16, max_slots=4)
    stream_toks = sum(r.max_new for r in reqs)
    static_toks = stream_toks / static_s
    cont_toks = stream_toks / cont_s
    st = server.stats
    add("serve_static_mixed", static_s * 1e6,
        {"tok_s": static_toks, "requests": n_req,
         "decode_traces": static_traces,
         "shape_buckets": static_traces})
    add("serve_continuous_mixed", cont_s * 1e6,
        {"tok_s": cont_toks, "requests": n_req,
         "decode_traces": batching.decode_trace_count(),
         "prefill_traces": batching.prefill_trace_count(),
         "decode_steps": st["decode_steps"],
         "pages_shared": st["pages_shared"],
         "peak_pages": st["peak_pages_in_use"],
         "speedup_vs_static": cont_toks / static_toks})

    # --- async driver: whole-prompt vs chunked prefill, SLO percentiles ---
    for label, chunk in (("whole", None), ("chunked", 16)):
        s, shorts, server, dt = _run_driver(cfg, soup, chunk, quick)
        st = server.stats
        add(f"serve_driver_{label}", dt * 1e6,
            {"tok_s": s["tokens_per_s"], "requests": s["requests"],
             "ttft_p50_ms": s["ttft_p50_ms"], "ttft_p99_ms": s["ttft_p99_ms"],
             "short_ttft_p50_ms": shorts["ttft_p50_ms"],
             "short_ttft_p99_ms": shorts["ttft_p99_ms"],
             "intertoken_p99_ms": s["intertoken_p99_ms"],
             "decode_traces": batching.decode_trace_count(),
             "prefill_traces": batching.prefill_trace_count(),
             "prefill_tokens": st["prefill_tokens"],
             "prefix_tokens_reused": st["prefix_tokens_reused"],
             "prefill_chunk": chunk or 0})
        if label == "chunked":
            # suffix-only prefill: resubmit a prompt sharing the first
            # long prompt's opening pages — they are parked on the
            # retained server's LRU, so the new admission must share
            # them and prefill ONLY the fresh suffix (token accounting
            # by the server's own counters, not wall clock)
            import numpy as np

            long0 = _driver_workload(cfg, quick)[0].tokens
            keep = (64 // server.page_size) * server.page_size
            re_prompt = np.concatenate([
                np.asarray(long0[:keep]),
                np.full((server.page_size,), 3, np.int32)])
            before = dict(st)
            server.run([batching.Request("resubmit", re_prompt, 4)])
            reused = st["prefix_tokens_reused"] - before["prefix_tokens_reused"]
            suffix = st["prefill_tokens"] - before["prefill_tokens"]
            results["serve_driver_chunked"]["resubmit_prefix_reused"] = reused
            results["serve_driver_chunked"]["resubmit_suffix_tokens"] = suffix
            results["serve_driver_chunked"]["resubmit_prompt_tokens"] = len(re_prompt)

    # --- population speculative decode vs plain ensemble, paged runtime --
    import jax.numpy as jnp

    # the limit case of WASH's same-basin population: ONE member stacked
    # N times, so the soup's argmax always agrees with the ensemble's and
    # the accept rate is deterministically 1.0 — the row isolates the
    # mechanism (k emitted tokens per ensemble dispatch instead of one);
    # trained populations land below this ceiling, which is why the
    # accept_rate column is reported rather than assumed
    member0 = jax.tree_util.tree_map(lambda x: x[0], popn)
    ident = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 4), member0)
    draft_k = 4

    def reqs_fn():
        return _spec_workload(cfg, quick)

    ens_sum, ens_server, ens_dt = _run_population(cfg, ident, reqs_fn, False)
    est = ens_server.stats
    add("serve_ensemble_paged", ens_dt * 1e6,
        {"tok_s": ens_sum["tokens_per_s"], "members": 4,
         "decode_steps": est["decode_steps"],
         "ttft_p99_ms": ens_sum["ttft_p99_ms"]})

    spec_sum, spec_server, spec_dt = _run_population(
        cfg, ident, reqs_fn, True, draft_k=draft_k)
    sst = spec_server.stats
    accept = sst["spec_accepted"] / max(sst["spec_drafted"], 1)
    add("serve_speculative", spec_dt * 1e6,
        {"tok_s": spec_sum["tokens_per_s"], "members": 4,
         "draft_k": draft_k, "accept_rate": accept,
         "drafted": sst["spec_drafted"], "accepted": sst["spec_accepted"],
         "decode_steps": sst["decode_steps"],
         "ttft_p99_ms": spec_sum["ttft_p99_ms"],
         "speedup_vs_ensemble":
             spec_sum["tokens_per_s"] / ens_sum["tokens_per_s"]})

    # --- quantized paged KV: int8 capacity at fixed HBM -------------------
    import time as _time

    ps_q = 4 if quick else 16
    reqs_q = _mixed_stream(cfg, n_req, max_prompt=prompt, max_new=max_new,
                           seed=1)
    max_pages_q = max(-(-(len(r.tokens) + r.max_new) // ps_q)
                      for r in reqs_q)
    q_server = batching.ContinuousServer(
        soup, cfg, page_size=ps_q, max_slots=4,
        num_pages=4 * max_pages_q + 8, max_pages_per_slot=max_pages_q,
        kv_dtype="int8")
    t0 = _time.perf_counter()
    q_out = q_server.run(reqs_q)
    q_s = _time.perf_counter() - t0
    assert len(q_out) == len(reqs_q)
    # capacity from LIVE pools' nbytes (int8 counts its scales) at
    # IDENTICAL geometry: a fresh fp32 sibling server, not an earlier
    # row's server whose page size differs
    ref_server = batching.ContinuousServer(
        soup, cfg, page_size=ps_q, max_slots=4,
        num_pages=q_server.num_pages, max_pages_per_slot=max_pages_q)
    per_page_fp32 = _pool_bytes(ref_server) / ref_server.num_pages
    per_page_int8 = _pool_bytes(q_server) / q_server.num_pages
    add("serve_quantized_kv", q_s * 1e6,
        {"tok_s": stream_toks / q_s,
         "kv_bytes_per_page_fp32": per_page_fp32,
         "kv_bytes_per_page_int8": per_page_int8,
         "capacity_ratio": per_page_fp32 / per_page_int8,
         "pages_per_gb_int8": int(2 ** 30 / per_page_int8),
         "pages_per_gb_fp32": int(2 ** 30 / per_page_fp32)})

    # --- telemetry overhead: same driver workload, obs on vs off ---------
    from repro import obs

    def best_driver_s(reps: int = 2) -> float:
        return min(_run_driver(cfg, soup, 16, quick)[3] for _ in range(reps))

    on_s = best_driver_s()
    tel = obs.get()
    tel.enabled = False
    try:
        off_s = best_driver_s()
    finally:
        tel.enabled = True
    add("serve_obs_overhead", (on_s - off_s) * 1e6,
        {"enabled_s": on_s, "disabled_s": off_s,
         "overhead_ratio": on_s / off_s})

    os.makedirs(os.path.dirname(JSON_OUT), exist_ok=True)
    with open(JSON_OUT, "w") as f:
        json.dump(with_provenance(
            {"batch": batch, "prompt": prompt, "max_new": max_new,
             "rows": results}), f, indent=2)
    return rows


def smoke() -> None:
    """CI fast-lane guard: tiny config, 8 new tokens, trace-count asserts
    for BOTH runtimes + the static-vs-continuous throughput win."""
    from repro.serving import batching
    from repro.serving import engine as serving

    cfg, popn, req = _problem(batch=2, prompt=8)
    soup = serving.averaged_params(popn)
    serving.reset_trace_counts()
    serving.clear_executable_cache()
    out = serving.generate(soup, cfg, req, 8)
    out2 = serving.generate(soup, cfg, req, 8)
    assert out.shape == out2.shape == (2, 16), out.shape
    assert serving.decode_trace_count() == 1, (
        f"scan decode must compile exactly once per shape, "
        f"traced {serving.decode_trace_count()}x"
    )
    assert serving.prefill_trace_count() == 1
    rows = run(quick=True)
    # assert on the structured JSON run() just wrote, not the formatted
    # row strings (a substring match on "decode_traces=1" would also pass
    # for 10+ traces — the exact regression this guard exists to catch)
    with open(JSON_OUT) as f:
        results = json.load(f)["rows"]
    cont = results["serve_continuous_mixed"]
    stat = results["serve_static_mixed"]
    assert cont["decode_traces"] == 1, (
        f"continuous decode must compile exactly once for the whole "
        f"mixed stream, traced {cont['decode_traces']}x"
    )
    assert cont["pages_shared"] > 0, (
        "the mixed stream shares prompt prefixes; dedup must trigger"
    )
    assert cont["tok_s"] > stat["tok_s"], (
        f"continuous ({cont['tok_s']:.0f} tok/s) must beat static "
        f"shape-bucketing ({stat['tok_s']:.0f} tok/s) on mixed traffic"
    )
    whole = results["serve_driver_whole"]
    chunked = results["serve_driver_chunked"]
    # the driver rows are warmed, so the timed pass must hit the shared
    # executable cache: ZERO new decode/prefill traces, not even one
    assert whole["decode_traces"] == 0 and chunked["decode_traces"] == 0, (
        f"warmed driver runs must not retrace decode "
        f"(whole {whole['decode_traces']}, chunked {chunked['decode_traces']})"
    )
    assert whole["prefill_traces"] == 0 and chunked["prefill_traces"] == 0, (
        f"warmed driver runs must not retrace prefill chunks "
        f"(whole {whole['prefill_traces']}, chunked {chunked['prefill_traces']})"
    )
    assert chunked["short_ttft_p99_ms"] < whole["short_ttft_p99_ms"], (
        f"chunked prefill must beat whole-prompt on the short requests' "
        f"tail TTFT (chunked p99 {chunked['short_ttft_p99_ms']:.1f}ms vs "
        f"whole {whole['short_ttft_p99_ms']:.1f}ms)"
    )
    assert chunked["resubmit_prefix_reused"] > 0, (
        "resubmitted prompt must share its LRU-retained prefix pages"
    )
    assert (chunked["resubmit_suffix_tokens"]
            == chunked["resubmit_prompt_tokens"]
            - chunked["resubmit_prefix_reused"]), (
        f"suffix-only prefill must compute exactly the uncached tokens: "
        f"prefilled {chunked['resubmit_suffix_tokens']} of "
        f"{chunked['resubmit_prompt_tokens']} with "
        f"{chunked['resubmit_prefix_reused']} reused"
    )
    ens = results["serve_ensemble_paged"]
    spec = results["serve_speculative"]
    # identical-member population + greedy => the soup's draft always
    # matches the ensemble's verify: the accept rate must be exactly 1
    # (any miss means the draft/verify sampling paths diverged)
    assert spec["accept_rate"] >= 0.999, (
        f"identical-member greedy population must accept every draft, "
        f"got accept_rate={spec['accept_rate']:.3f} "
        f"({spec['accepted']}/{spec['drafted']})"
    )
    assert spec["decode_steps"] < ens["decode_steps"], (
        f"speculation must emit multiple tokens per ensemble dispatch "
        f"(spec {spec['decode_steps']} steps vs plain {ens['decode_steps']})"
    )
    assert spec["tok_s"] > ens["tok_s"], (
        f"speculative decode ({spec['tok_s']:.0f} tok/s) must beat the "
        f"plain paged ensemble ({ens['tok_s']:.0f} tok/s) at accept~1"
    )
    quant = results["serve_quantized_kv"]
    # int8 pages carry a per-page f32 scale, so the ratio sits just under
    # the dtype's 4x; anything <= 3 means the pools aren't quantized
    assert quant["capacity_ratio"] > 3.0, (
        f"int8 paged KV must fit >3x the pages of fp32 at fixed HBM, "
        f"got {quant['capacity_ratio']:.2f}x"
    )
    overhead = results["serve_obs_overhead"]["overhead_ratio"]
    # registry observes are a handful of dict ops per decode step; the
    # generous bound absorbs CPU wall-clock noise on these tiny shapes
    assert overhead < 1.5, (
        f"telemetry overhead ratio {overhead:.3f} exceeds the 1.5x smoke "
        f"bound — instrumentation is supposed to be a few host-side ops"
    )
    from benchmarks._util import print_rows

    print_rows(rows)
    print(f"# serving smoke OK; wrote {JSON_OUT}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        from benchmarks._util import print_rows

        print_rows(run(quick=not args.full))
