"""CLI launcher: train a WASH population of any assigned architecture.

CPU-scale entry point (reduced configs train locally; full configs are
exercised through the dry-run).  Copy-pasteable examples:

  python -m repro.launch.train --arch llama3.2-3b --reduced \\
      --population 4 --mixing wash --base-p 0.01 --steps 200

  python -m repro.launch.train --arch qwen3-4b --reduced --mixing papa \\
      --steps 100 --optimizer adamw --lr 3e-4

  python -m repro.launch.train --arch llama3.2-3b --reduced \\
      --engine shard_map --mesh ens_dp --steps 50 \\
      --ckpt-population /tmp/pop.npz

Every flag is documented with its default: ``--help``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro.configs import INPUT_SHAPES_BY_NAME, get_arch
from repro.configs.base import TrainConfig
from repro.core.mixing import MixingConfig
from repro.data import make_lm_task, sample_tokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.specs import concrete_batch
from repro.models import transformer as M
from repro.serving import averaged_params
from repro.train import checkpoint, train_population


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--arch", required=True,
                    help="architecture name from repro.configs (e.g. "
                         "llama3.2-3b, qwen3-4b, deepseek-v2-lite-16b)")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-scale) variant")
    ap.add_argument("--population", type=int, default=4,
                    help="population size N (members trained in parallel)")
    ap.add_argument("--mixing", default="wash",
                    choices=["none", "wash", "wash_opt", "papa", "papa_all"],
                    help="mixing method: wash (paper Eq. 3), wash_opt "
                         "(shuffle optimizer moments too), papa/papa_all "
                         "(parameter-averaging baselines), none")
    ap.add_argument("--base-p", type=float, default=0.01,
                    help="WASH base shuffle probability p (paper Eq. 6)")
    ap.add_argument("--schedule", default="decreasing",
                    choices=["decreasing", "constant", "increasing"],
                    help="layer-wise shuffle-probability schedule")
    ap.add_argument("--mode", default="dense", choices=["dense", "bucketed"],
                    help="shuffle plan mode: dense per-coordinate permutes "
                         "or bucketed cyclic shifts (TPU-native)")
    ap.add_argument("--engine", default="vmap", choices=["vmap", "shard_map"],
                    help="vmap: two-jit reference loop; shard_map: fused "
                         "single-jit collective engine (forces bucketed "
                         "plans for wash kinds)")
    ap.add_argument("--steps", type=int, default=200,
                    help="total optimizer steps per member")
    ap.add_argument("--record-every", type=int, default=None,
                    help="history record period (default: steps // 10); also "
                         "the fused engine's chunk window length")
    ap.add_argument("--sync-staging", action="store_true",
                    help="shard_map engine: force synchronous per-chunk "
                         "staging; default auto-gates the double-buffered "
                         "staging thread (off on CPU when chunks are too "
                         "short to amortize the thread handoff)")
    ap.add_argument("--no-gate-split", action="store_true",
                    help="shard_map engine: keep one dispatch per record "
                         "window instead of splitting no-mix gate runs onto "
                         "the collective-free executable")
    ap.add_argument("--mesh", default="ens",
                    choices=["ens", "ens_dp", "ens_dp_mp", "ens_pp",
                             "ens_dp_pp"],
                    help="shard_map engine: host mesh layout (ens-only, "
                         "ens+data, or ens+data+model; clamped to the "
                         "host's device count).  ens_dp_mp also shards "
                         "params via repro.sharding.rules and mixes with "
                         "shard-local plans (core.shardplan).  ens_pp/"
                         "ens_dp_pp add a pipeline-stage axis and route "
                         "through the microbatched pipelined engine")
    ap.add_argument("--mesh-shape", default=None,
                    help="explicit comma-separated axis sizes matching the "
                         "--mesh kind's axes (e.g. 2,2,2 for ens_dp_mp, "
                         "2,4 for ens_pp) instead of the automatic fill; "
                         "must divide the host's device count")
    ap.add_argument("--pp-stages", type=int, default=None,
                    help="pipeline stages S for --mesh ens_pp/ens_dp_pp "
                         "(default 1; must divide the devices left after "
                         "the ens axis and the model's layer count)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="pipelined engine: microbatches M per optimizer "
                         "step (GPipe schedule of M+S-1 ticks; must divide "
                         "--batch-size)")
    ap.add_argument("--pallas-shuffle", action="store_true",
                    help="apply bucketed shuffles through the fused Pallas "
                         "kernel (kernels.wash_shuffle; interpret mode "
                         "auto-detects off-TPU hosts)")
    ap.add_argument("--batch-size", type=int, default=8,
                    help="per-member batch size (synthetic LM task)")
    ap.add_argument("--seq-len", type=int, default=64,
                    help="training sequence length")
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "adamw"],
                    help="member optimizer")
    ap.add_argument("--lr", type=float, default=0.05,
                    help="peak learning rate")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for init, data, and shuffle plans")
    ap.add_argument("--ckpt", default=None,
                    help="save the averaged model (soup) here (.npz)")
    ap.add_argument("--ckpt-population", default=None,
                    help="save the full stacked population here (.npz) — "
                         "the input format of repro.launch.serve --ckpt, "
                         "which needs all members for member/ensemble modes")
    ap.add_argument("--history", default=None,
                    help="dump the training history (loss/consensus/comm "
                         "per record window) as JSON here")
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry event stream (spans, compile "
                         "events, comm-volume checkpoints, final metric "
                         "snapshots) as JSONL here; validate with "
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
    key = jax.random.key(args.seed)

    task = make_lm_task(jax.random.fold_in(key, 1), vocab=min(cfg.vocab_size, 512))

    def data_fn(m, step, k):
        b = concrete_batch(cfg, jax.random.fold_in(k, 10), args.batch_size, args.seq_len)
        b["tokens"] = sample_tokens(task, k, args.batch_size, args.seq_len) % cfg.vocab_size
        return b

    def loss_fn(params, batch):
        loss, _ = M.loss_fn(params, cfg, batch)
        return loss

    tcfg = TrainConfig(
        population=args.population, optimizer=args.optimizer, lr=args.lr,
        total_steps=args.steps, batch_size=args.batch_size, seq_len=args.seq_len,
        seed=args.seed,
    )
    mcfg = MixingConfig(kind=args.mixing, base_p=args.base_p,
                        schedule=args.schedule, mode=args.mode,
                        pallas_shuffle=args.pallas_shuffle)
    if (args.engine == "shard_map" and args.mixing in ("wash", "wash_opt")
            and args.mode != "bucketed"):
        print("note: engine=shard_map lowers bucketed plans only; "
              "switching --mode dense -> bucketed")
        mcfg = dataclasses.replace(mcfg, mode="bucketed")
    # read mcfg.mode, not args.mode: the shard_map engine auto-coerces
    # dense wash configs to bucketed just above
    if args.pallas_shuffle and mcfg.mode == "dense":
        ap.error("--pallas-shuffle fuses bucketed applies; use --mode bucketed")

    pipelined = args.mesh in ("ens_pp", "ens_dp_pp")
    if (args.pp_stages is not None or args.microbatches > 1) and not pipelined:
        ap.error("--pp-stages/--microbatches require --mesh ens_pp or "
                 "ens_dp_pp")
    mesh_shape = None
    if args.mesh_shape is not None:
        try:
            mesh_shape = tuple(int(x) for x in args.mesh_shape.split(","))
        except ValueError:
            ap.error(f"--mesh-shape {args.mesh_shape!r} is not a "
                     "comma-separated list of integers")

    engine_opts = None
    mesh = None
    if args.engine == "shard_map":
        engine_opts = {
            # False forces sync; None = engine.resolve_async_staging gate
            "async_staging": False if args.sync_staging else None,
            "split_gate_runs": not args.no_gate_split,
            "pallas_shuffle": args.pallas_shuffle,
        }
        if args.mesh != "ens" or mesh_shape is not None:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(args.population, args.mesh,
                                  mesh_shape=mesh_shape,
                                  pp_stages=args.pp_stages)
            if "model" in mesh.axis_names and mesh.shape["model"] > 1:
                from repro.sharding import rules

                params_sds = jax.eval_shape(
                    lambda: M.init_params(jax.random.key(0), cfg)
                )
                engine_opts["param_specs"] = rules.param_pspecs(
                    params_sds, cfg, mesh
                )
            print(f"mesh: {dict(mesh.shape)}")
    elif (args.sync_staging or args.no_gate_split or args.mesh != "ens"
          or mesh_shape is not None):
        ap.error("--sync-staging/--no-gate-split/--mesh/--mesh-shape "
                 "require --engine shard_map")
    if args.record_every is not None and args.record_every < 1:
        ap.error("--record-every must be >= 1")
    record_every = (
        args.record_every if args.record_every is not None
        else max(args.steps // 10, 1)
    )
    if pipelined:
        from repro.train import StageFns, train_population_pipelined

        res = train_population_pipelined(
            key, lambda k: M.init_params(k, cfg),
            StageFns(*M.pipeline_stage_fns(cfg)), data_fn,
            tcfg, mcfg, cfg.num_layers, record_every=record_every,
            mesh=mesh, microbatches=args.microbatches,
            async_staging=engine_opts["async_staging"],
            split_gate_runs=engine_opts["split_gate_runs"],
            pallas_shuffle=engine_opts["pallas_shuffle"],
        )
    else:
        res = train_population(
            key, lambda k: M.init_params(k, cfg), loss_fn, data_fn,
            tcfg, mcfg, cfg.num_layers, record_every=record_every,
            engine=args.engine, mesh=mesh, engine_opts=engine_opts,
        )

    soup = averaged_params(res)
    print(f"arch={cfg.name} mixing={args.mixing} steps={args.steps} "
          f"engine={args.engine}")
    print(f"final mean member loss : {res.history['loss'][-1]:.4f}")
    print(f"consensus distance     : {res.history['consensus'][-1]:.4f}")
    print(f"scalars sent per member: {res.comm_scalars:.3e}")

    eval_batch = data_fn(0, 0, jax.random.fold_in(key, 777))
    loss_soup, _ = M.loss_fn(soup, cfg, eval_batch)
    print(f"averaged-model loss    : {float(loss_soup):.4f}")

    if args.ckpt:
        written = checkpoint.save(args.ckpt, soup)
        print(f"saved averaged model -> {written}")
    if args.ckpt_population:
        written = checkpoint.save(args.ckpt_population, res.population)
        print(f"saved population -> {written}")
    if args.history:
        os.makedirs(os.path.dirname(args.history) or ".", exist_ok=True)
        with open(args.history, "w") as f:
            json.dump(res.history, f, indent=2)

    tel.finalize()
    if args.metrics_out:
        print(f"wrote telemetry stream -> {args.metrics_out}")


if __name__ == "__main__":
    main()
