"""CLI launcher: serve a WASH population (scan engine or continuous batching).

Loads (or random-inits / quick-trains) a population of the assigned
architecture and serves synthetic prompts under a serving mode, reporting
tokens/sec and the engine's compile behavior.  Two runtimes:

  * default — the fused scan engine (`repro.serving.engine`): one compiled
    decode program per request shape, for shape-uniform batches;
  * ``--continuous`` — the continuous-batching runtime over a paged KV
    cache (`repro.serving.batching`): a mixed-length request stream is
    admitted into ``--max-slots`` slots and decoded with exactly one
    compiled step program, page tables and all lengths traced;
  * ``--driver`` — the async request driver (`repro.serving.driver`) on
    top of the continuous runtime: timed (``--arrival-rate``) arrivals,
    chunked prefill (``--prefill-chunk``) interleaved with in-flight
    decode, LRU page retention (``--retain-pages``), and per-request
    TTFT/latency percentiles instead of aggregate tokens/sec alone.

Copy-pasteable examples:

  python -m repro.launch.serve --arch llama3.2-3b --reduced \\
      --population 4 --mode soup --batch-size 8 --max-new 32

  python -m repro.launch.serve --arch qwen3-4b --reduced --mode ensemble \\
      --temperature 0.7 --seed 3 --mesh data

  python -m repro.launch.serve --arch llama3.2-3b --reduced --compare

  python -m repro.launch.serve --arch llama3.2-3b --reduced --continuous \\
      --requests 16 --max-slots 4 --page-size 16 --max-new 32

  python -m repro.launch.serve --arch llama3.2-3b --reduced --driver \\
      --arrival-rate 50 --prefill-chunk 16 --retain-pages --requests 16

``--ckpt`` restores a *population* checkpoint (a stacked pytree written by
``repro.train.checkpoint.save``, e.g. ``--ckpt-population`` from the train
CLI); without it members are random-init (throughput numbers are
weight-independent) unless ``--train-steps`` quick-trains first.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import TrainConfig
from repro.core.mixing import MixingConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import concrete_batch
from repro.models import transformer as M
from repro.serving import batching
from repro.serving import engine as serving
from repro.train import checkpoint, train_population


def _population(args, cfg, key):
    init = lambda k: M.init_params(k, cfg)  # noqa: E731
    if args.ckpt:
        # restore only reads shapes/dtypes from the template: eval_shape
        # costs nothing, vs actually random-initializing N full models
        like = jax.eval_shape(
            lambda: jax.vmap(init)(jax.random.split(key, args.population))
        )
        popn = checkpoint.restore(args.ckpt, like)
        print(f"restored population <- {args.ckpt}")
        return popn
    if args.train_steps > 0:
        from repro.data import make_lm_task, sample_tokens

        task = make_lm_task(jax.random.fold_in(key, 1),
                            vocab=min(cfg.vocab_size, 512))

        def data_fn(m, step, k):
            b = concrete_batch(cfg, jax.random.fold_in(k, 10), 8, 32)
            b["tokens"] = sample_tokens(task, k, 8, 32) % cfg.vocab_size
            return b

        def loss_fn(params, batch):
            loss, _ = M.loss_fn(params, cfg, batch)
            return loss

        res = train_population(
            key, init, loss_fn, data_fn,
            TrainConfig(population=args.population, optimizer="sgd", lr=0.05,
                        total_steps=args.train_steps),
            MixingConfig(kind="wash", base_p=0.05, mode="bucketed"),
            cfg.num_layers, record_every=max(args.train_steps // 2, 1),
        )
        return res.population
    return jax.vmap(init)(jax.random.split(key, args.population))


def _serve_once(popn, cfg, batch, args, mode, mesh, key):
    # resolve the mode's params ONCE (soup averaging / member slicing is
    # per-deployment work, not per-request work), then time generate —
    # the steady-state number measures the decode engine alone
    params = serving.serving_params(popn, mode, args.member)
    gen_mode = "ensemble" if mode == "ensemble" else "soup"

    def request():
        out = serving.generate(
            params, cfg, batch, args.max_new, temperature=args.temperature,
            key=key, mode=gen_mode, mesh=mesh,
        )
        jax.block_until_ready(out)
        return out

    t0 = time.time()
    out = request()
    warm = time.time() - t0
    t0 = time.time()
    out = request()
    dt = max(time.time() - t0, 1e-9)
    toks = args.batch_size * args.max_new
    print(f"mode={mode:9s} {toks / dt:9.1f} tok/s  "
          f"(compile+first {warm:.2f}s, steady {dt:.3f}s/req, "
          f"decode traces {serving.decode_trace_count()}, "
          f"executables {serving.executable_cache_size()})")
    return out


def mixed_stream(cfg, n_requests: int, max_prompt: int, max_new: int,
                 seed: int, temperature: float = 0.0,
                 share_prefix_every: int = 0):
    """A synthetic mixed-length request stream: prompt lengths and token
    budgets drawn uniformly, per-request keys when sampling — the traffic
    shape the static engine cannot serve without padding or re-compiling.

    ``share_prefix_every=k`` makes every k-th request reuse one common
    prompt prefix, so the runtime's prefix-page dedup has something to
    find (benchmarks use this; the CLI default leaves prompts independent).
    The single source of the traffic shape — ``benchmarks/serving_bench``
    consumes this function, so the CLI and the bench measure one stream.
    """
    rng = np.random.default_rng(seed)
    common = rng.integers(0, cfg.vocab_size, size=(max_prompt,)).astype(np.int32)
    reqs = []
    for i in range(n_requests):
        S = int(rng.integers(max(2, max_prompt // 4), max_prompt + 1))
        mn = int(rng.integers(max(1, max_new // 4), max_new + 1))
        if share_prefix_every and i % share_prefix_every == 0:
            prompt = common[:S].copy()
        else:
            prompt = rng.integers(0, cfg.vocab_size, size=(S,)).astype(np.int32)
        key = jax.random.key(1000 + i) if temperature > 0 else None
        reqs.append(batching.Request(i, prompt, mn, key=key))
    return reqs


def _serve_continuous(popn, cfg, args):
    # page-table width = the stream's worst-case context, not the whole
    # pool: the attend is O(max_pages_per_slot * page_size) per slot
    max_pages = -(-(args.seq_len + args.max_new) // args.page_size)
    server = batching.ContinuousServer.from_trained(
        popn, cfg, mode=args.mode, member=args.member,
        temperature=args.temperature, page_size=args.page_size,
        max_slots=args.max_slots, num_pages=args.num_pages,
        max_pages_per_slot=max_pages, speculative=args.speculative,
        draft_k=args.draft_k, kv_dtype=args.kv_dtype,
    )
    reqs = mixed_stream(cfg, args.requests, args.seq_len, args.max_new,
                        args.seed, args.temperature)
    batching.reset_trace_counts()
    t0 = time.time()
    out = server.run(reqs)
    dt = max(time.time() - t0, 1e-9)
    toks = sum(r.max_new for r in reqs)
    st = server.stats
    print(f"continuous mode={args.mode} requests={len(reqs)} "
          f"slots={args.max_slots} page_size={args.page_size} "
          f"pool={args.num_pages} kv_dtype={args.kv_dtype or 'param'}")
    print(f"  {toks / dt:9.1f} tok/s  ({dt:.2f}s stream, "
          f"{st['decode_steps']} decode steps, "
          f"decode traces {batching.decode_trace_count()}, "
          f"prefill traces {batching.prefill_trace_count()})")
    print(f"  pages: allocated {st['pages_allocated']}, "
          f"shared {st['pages_shared']}, peak {st['peak_pages_in_use']}")
    if args.speculative:
        drafted = max(st["spec_drafted"], 1)
        print(f"  speculative draft_k={args.draft_k}: accepted "
              f"{st['spec_accepted']}/{st['spec_drafted']} drafts "
              f"({st['spec_accepted'] / drafted:.0%})")
    assert len(out) == len(reqs)
    return out


def _serve_driver(popn, cfg, args):
    """Serve the mixed stream through the async request driver: timed
    (Poisson or back-to-back) arrivals, chunked prefill interleaved with
    decode, per-request TTFT/latency percentiles from the driver's
    metrics — the SLO view of the same runtime ``--continuous`` measures
    for throughput."""
    from repro.serving.driver import RequestDriver, poisson_arrivals, summarize

    max_pages = -(-(args.seq_len + args.max_new) // args.page_size)
    server = batching.ContinuousServer.from_trained(
        popn, cfg, mode=args.mode, member=args.member,
        temperature=args.temperature, page_size=args.page_size,
        max_slots=args.max_slots, num_pages=args.num_pages,
        max_pages_per_slot=max_pages, retain_pages=args.retain_pages,
        speculative=args.speculative, draft_k=args.draft_k,
        kv_dtype=args.kv_dtype,
    )
    reqs = mixed_stream(cfg, args.requests, args.seq_len, args.max_new,
                        args.seed, args.temperature, share_prefix_every=4)
    chunk = args.prefill_chunk if args.prefill_chunk > 0 else None
    driver = RequestDriver(server, prefill_chunk=chunk)
    arrivals = (poisson_arrivals(reqs, args.arrival_rate, seed=args.seed)
                if args.arrival_rate > 0 else reqs)
    batching.reset_trace_counts()
    metrics = driver.run(arrivals)
    s = summarize(metrics)
    st = server.stats
    print(f"driver mode={args.mode} requests={s['requests']} "
          f"slots={args.max_slots} chunk={chunk} "
          f"arrival_rate={args.arrival_rate or 'back-to-back'}")
    print(f"  {s['tokens_per_s']:9.1f} tok/s  "
          f"ttft p50 {s['ttft_p50_ms']:.1f}ms p99 {s['ttft_p99_ms']:.1f}ms  "
          f"intertoken p99 {s['intertoken_p99_ms']:.2f}ms  "
          f"latency p99 {s['latency_p99_ms']:.1f}ms")
    print(f"  decode traces {batching.decode_trace_count()}, "
          f"prefill traces {batching.prefill_trace_count()}, "
          f"prefill tokens {st['prefill_tokens']} "
          f"(prefix reused {st['prefix_tokens_reused']}), "
          f"lru hits {st['lru_hits']} evictions {st['lru_evictions']}")
    assert s["requests"] == len(reqs)
    # suffix-prefill configs decode through ONE program for the stream
    # (a fresh process compiles it exactly once — the CI driver smoke
    # rides on this)
    assert batching.decode_trace_count() <= 1
    return metrics


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--arch", required=True,
                    help="architecture name from repro.configs (e.g. "
                         "llama3.2-3b, qwen3-4b)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced (CPU-scale) config variant")
    ap.add_argument("--population", type=int, default=4,
                    help="population size N (members to init/restore)")
    ap.add_argument("--mode", default="soup", choices=list(serving.MODES),
                    help="serving mode: soup (1x cost), member (one member), "
                         "ensemble (Nx decode, averaged logits)")
    ap.add_argument("--member", type=int, default=0,
                    help="which member --mode member serves")
    ap.add_argument("--mesh", default="none", choices=["none", "data"],
                    help="data: shard the request batch over every host "
                         "device (launch.mesh.make_host_data_mesh); static "
                         "engine only")
    ap.add_argument("--pp-stages", type=int, default=0,
                    help="stage-split decode over this many pipeline stages "
                         "on a (pipe,) mesh: blocks + KV cache sliced 1/S "
                         "per chip, bitwise-identical tokens; static engine "
                         "only, attn families, num_layers %% S == 0")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (keyless)")
    ap.add_argument("--max-new", type=int, default=32,
                    help="new tokens per request (continuous: the maximum "
                         "of the per-request budget range)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="static engine: requests per shape-uniform batch")
    ap.add_argument("--seq-len", type=int, default=32,
                    help="prompt length (continuous: the maximum of the "
                         "per-request prompt-length range)")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for weights, prompts, and stream shape")
    ap.add_argument("--ckpt", default=None,
                    help="restore a stacked-population .npz (from "
                         "launch.train --ckpt-population)")
    ap.add_argument("--train-steps", type=int, default=0,
                    help="quick-train the population this many steps first")
    ap.add_argument("--compare", action="store_true",
                    help="static engine: serve the same batch under every "
                         "mode (the soup-vs-ensemble trade, measured)")
    ap.add_argument("--continuous", action="store_true",
                    help="serve a mixed-length request stream through the "
                         "continuous-batching paged-KV runtime")
    ap.add_argument("--requests", type=int, default=16,
                    help="continuous: number of requests in the stream")
    ap.add_argument("--max-slots", type=int, default=4,
                    help="continuous: in-flight request slots (the decode "
                         "step's batch size)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="continuous: tokens per KV page")
    ap.add_argument("--num-pages", type=int, default=256,
                    help="continuous: KV page-pool size shared by all slots")
    ap.add_argument("--driver", action="store_true",
                    help="serve the stream through the async request driver "
                         "(timed arrivals, chunked prefill interleaved with "
                         "decode, TTFT/latency percentiles)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="driver: Poisson arrival rate in requests/sec "
                         "(0 = submit the whole stream back-to-back)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="driver: prefill at most this many prompt tokens "
                         "per tick, interleaved with decode steps "
                         "(0 = whole remaining suffix in one program)")
    ap.add_argument("--speculative", action="store_true",
                    help="continuous/driver: population-powered speculative "
                         "decoding — the soup drafts --draft-k tokens per "
                         "step, the ensemble verifies them in one batched "
                         "step (bitwise the plain path at fp32 KV)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative draft length (tokens proposed per "
                         "decode call; one executable per distinct value)")
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="quantize the paged KV pools (int8, one scale per "
                         "page — double the effective pool capacity; "
                         "default: the model's param dtype, bitwise)")
    ap.add_argument("--retain-pages", action="store_true",
                    help="driver: keep refcount-0 prefix pages on an LRU "
                         "list (evicted only under pool pressure) so "
                         "recurring prompts skip their prefill compute")
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry event stream (spans, compile "
                         "events, SLO histograms, final metric snapshots) "
                         "as JSONL here; validate with "
                         "tools/check_metrics_schema.py")
    ap.add_argument("--metrics-summary", action="store_true",
                    help="print a telemetry metric summary on exit "
                         "(repro.obs console sink)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the first "
                         "instrumented spans into this directory (bounded "
                         "window; view with TensorBoard or Perfetto)")
    args = ap.parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}")

    from repro import obs

    tel = obs.configure(jsonl=args.metrics_out,
                        console=args.metrics_summary,
                        profile_dir=args.profile_dir)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if (args.speculative or args.kv_dtype) and not (args.continuous
                                                    or args.driver):
        ap.error("--speculative/--kv-dtype are continuous-runtime knobs; "
                 "add --continuous or --driver")

    key = jax.random.key(args.seed)
    if args.temperature > 0.0:
        sample_key = jax.random.fold_in(key, 999)
    else:
        sample_key = None

    popn = _population(args, cfg, key)

    if args.driver:
        if args.mesh != "none" or args.pp_stages:
            ap.error("--driver does not take --mesh/--pp-stages "
                     "(single-host runtime)")
        try:
            _serve_driver(popn, cfg, args)
        finally:
            tel.finalize()
            if args.metrics_out:
                print(f"wrote telemetry stream -> {args.metrics_out}")
        return

    if args.continuous:
        if args.mesh != "none" or args.pp_stages:
            ap.error("--continuous does not take --mesh/--pp-stages "
                     "(single-host runtime)")
        try:
            _serve_continuous(popn, cfg, args)
        finally:
            tel.finalize()
            if args.metrics_out:
                print(f"wrote telemetry stream -> {args.metrics_out}")
        return

    batch = concrete_batch(cfg, jax.random.fold_in(key, 2),
                           args.batch_size, args.seq_len)

    mesh = None
    if args.pp_stages:
        if args.mesh != "none":
            ap.error("--pp-stages builds its own (pipe,) mesh; drop --mesh")
        from jax.sharding import AxisType

        if args.pp_stages < 1 or args.pp_stages > len(jax.devices()):
            ap.error(f"--pp-stages {args.pp_stages} needs that many "
                     f"devices; this host has {len(jax.devices())}")
        mesh = jax.make_mesh((args.pp_stages,), ("pipe",),
                             axis_types=(AxisType.Auto,))
        print(f"mesh: {dict(mesh.shape)}")
    elif args.mesh == "data":
        from repro.launch.mesh import make_host_data_mesh

        mesh = make_host_data_mesh()
        print(f"mesh: {dict(mesh.shape)}")

    print(f"arch={cfg.name} population={args.population} "
          f"B={args.batch_size} S={args.seq_len} new={args.max_new} "
          f"temperature={args.temperature}")
    serving.reset_trace_counts()
    modes = list(serving.MODES) if args.compare else [args.mode]
    outs = {m: _serve_once(popn, cfg, batch, args, m, mesh, sample_key)
            for m in modes}
    if args.compare:
        soup, ens = np.asarray(outs["soup"]), np.asarray(outs["ensemble"])
        agree = float((soup[:, args.seq_len:] == ens[:, args.seq_len:]).mean())
        print(f"soup/ensemble token agreement: {agree:.0%}")

    tel.finalize()
    if args.metrics_out:
        print(f"wrote telemetry stream -> {args.metrics_out}")


if __name__ == "__main__":
    main()
