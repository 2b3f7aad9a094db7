import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) and emit
roofline terms.  The two lines above MUST stay first: jax locks the device
count on first init, and only the dry-run wants 512 placeholder devices.

Cost accounting: XLA's cost_analysis counts a rolled scan body ONCE, so a
full-depth rolled compile under-reports FLOPs/bytes by ~num_layers.  Each
pair therefore compiles three artifacts:

  1. full depth, rolled  — the PROOF that the production graph lowers,
     partitions and fits (memory_analysis comes from this one);
  2. depth-1 and depth-2, fully unrolled — their difference is exactly one
     layer's per-device FLOPs/bytes/collectives, so
         total(L) = cost(d1) + (L-1) · (cost(d2) - cost(d1))
     is exact for homogeneous stacks (validated against a full unroll in
     EXPERIMENTS.md §Dry-run).

Pass --full-unroll to skip extrapolation and unroll all layers (slow; used
for the validation run and the WASH population step, whose shuffle traffic
is depth-dependent through the Eq. 6 schedule).

Usage:
  python -m repro.launch.dryrun --arch minitron-8b --shape train_4k
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --wash 2 --multi-pod --full-unroll
  python -m repro.launch.dryrun --all [--multi-pod] --out-dir benchmarks/dryrun
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, INPUT_SHAPES_BY_NAME, get_arch
from repro.configs.base import InputShape, ModelConfig
from repro.core.layer_index import infer_layer_ids, total_layers
from repro.core.mixing import MixingConfig, mix_once
from repro.launch import hlo_stats
from repro.launch.mesh import make_ensemble_mesh, make_production_mesh
from repro.launch.specs import input_specs
from repro.models import transformer as M
from repro.optim import make_optimizer
from repro.sharding import rules
from jax.sharding import NamedSharding, PartitionSpec as P

# dense archs get an explicit sliding-window variant for long_500k
SWA_WINDOW = 8192
LONG_OK_FAMILIES = ("ssm", "hybrid")
LONG_SKIP = {
    "whisper-medium": "enc-dec full-attention decoder; 500k decode out of family",
    "deepseek-v2-lite-16b": "MLA latent cache is full attention over 500k (no SWA claim)",
    "kimi-k2-1t-a32b": "full-attention MoE; no sub-quadratic variant claimed",
    "internvl2-76b": "full-attention VLM backbone; long-context not in scope",
}

_EXTRAP_KEYS = (
    "hlo_flops", "hlo_bytes", "collective_bytes", "global_flops",
    "bytes_all-gather", "bytes_all-reduce", "bytes_reduce-scatter",
    "bytes_all-to-all", "bytes_collective-permute", "bytes_crosspod",
    "compute_s", "memory_s", "collective_s",
)


def variant_for(cfg: ModelConfig, shape: InputShape):
    """Returns (cfg, note) or (None, skip_reason)."""
    if shape.name != "long_500k":
        return cfg, ""
    if cfg.name in LONG_SKIP:
        return None, LONG_SKIP[cfg.name]
    if cfg.family in LONG_OK_FAMILIES:
        return cfg, "sub-quadratic native (SSM state / SWA)"
    return dataclasses.replace(cfg, window=SWA_WINDOW), f"SWA variant (window={SWA_WINDOW})"


def depth_variant(cfg: ModelConfig, d: int) -> ModelConfig:
    return dataclasses.replace(
        cfg,
        num_layers=d,
        encoder_layers=d if cfg.is_encdec else 0,
        scan_unroll=d,
    )


def params_shapes(cfg: ModelConfig):
    return jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))


def opt_shapes(params_sds, optimizer: str):
    init, _ = make_optimizer(optimizer)
    return jax.eval_shape(init, params_sds)


def _count(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))


def active_params(cfg: ModelConfig, params_sds) -> int:
    """N_active for the 6·N·D rule: routed experts count top_k/E."""
    total = _count(params_sds)
    if not cfg.moe:
        return total
    flat, _ = jax.tree_util.tree_flatten_with_path(params_sds)
    routed = sum(
        int(l.size)
        for p, l in flat
        if any(hasattr(q, "key") and str(q.key) == "experts" for q in p)
    )
    return total - routed + routed * cfg.top_k // max(cfg.n_routed_experts, 1)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, optimizer: str = "adamw"):
    _, opt_update = make_optimizer(optimizer)

    def train_step(params, opt_state, batch):
        def lf(p):
            loss, _ = M.loss_fn(p, cfg, batch)
            return loss

        loss, grads = jax.value_and_grad(lf)(params)
        params, opt_state = opt_update(params, grads, opt_state, 3e-4)
        return params, opt_state, loss

    return train_step


def make_prefill_step(cfg: ModelConfig, capacity: int):
    def prefill_step(params, batch):
        return M.prefill(params, cfg, batch, capacity=capacity)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, tokens, cache, pos):
        return M.decode_step(params, cfg, tokens, cache, pos)

    return serve_step


def make_wash_step(cfg: ModelConfig, n: int, mcfg: MixingConfig, optimizer: str = "adamw",
                   mix_fn=None):
    """Population train step: vmapped member update + bucketed WASH shuffle.

    The stacked ens axis is sharded over the mesh's ens axis; the bucketed
    shuffle's jnp.roll over that axis lowers to collective-permute — the
    paper's peer-to-peer exchange, measurable in the HLO.
    """
    _, opt_update = make_optimizer(optimizer)
    params_sds = params_shapes(cfg)
    lids = infer_layer_ids(params_sds, cfg.num_layers)
    tl = total_layers(cfg.num_layers)

    def wash_step(pop, pop_opt, batch, key):
        def one(p, s, b):
            def lf(pp):
                loss, _ = M.loss_fn(pp, cfg, b)
                return loss

            loss, g = jax.value_and_grad(lf)(p)
            p2, s2 = opt_update(p, g, s, 3e-4)
            return p2, s2, loss

        pop, pop_opt, losses = jax.vmap(one)(pop, pop_opt, batch)
        if mix_fn is not None:
            pop, pop_opt, comm = mix_fn(pop, pop_opt, key)
        else:
            pop, pop_opt, comm = mix_once(key, pop, pop_opt, mcfg, lids, tl)
        return pop, pop_opt, jnp.mean(losses), comm

    return wash_step


def make_shardlocal_mixer(cfg: ModelConfig, mcfg: MixingConfig, mesh,
                          pop_specs, opt_specs):
    """§Perf: shard-local WASH shuffle under shard_map.

    The stacked-bucketed shuffle gathers globally-indexed coordinates,
    which breaks the parameter sharding and makes XLA replicate the
    selected payload over each member's chips before the ens-axis permute
    (measured: 0.18 GB/chip instead of ~0.7 MB/chip).  Thin delegator to
    the real subsystem, :func:`repro.core.shardplan.make_shardlocal_mixer`
    (which also fixed this prototype's bugs: plan keys now fold the *per
    leaf* shard position so replicas of an unsharded leaf stay consistent,
    and the comm count is the exact host-side total instead of a per-chip
    psum that double-counted data replicas).  Model-config adaptation is
    the only logic left here.
    """
    from repro.core.shardplan import make_shardlocal_mixer as _mk

    return _mk(mesh, mcfg, cfg.num_layers, pop_specs, opt_specs)


# ---------------------------------------------------------------------------
# single compile
# ---------------------------------------------------------------------------


def compile_once(cfg: ModelConfig, shape: InputShape, mesh, wash: int = 0,
                 mixing_kind: str = "wash"):
    """Lower + compile one step; return (stats_dict, memory_dict)."""
    import contextlib
    from repro.launch.mesh import data_axes
    from repro.sharding import hints

    chips = mesh.size
    params_sds = params_shapes(cfg)
    pspecs = rules.param_pspecs(params_sds, cfg, mesh)

    if cfg.shard_hints:
        # with_sharding_constraint(P(...)) needs an ambient mesh
        with jax.set_mesh(mesh), hints.use_hints(data_axes(mesh), "model"):
            return _compile_inner(cfg, shape, mesh, wash, mixing_kind, chips,
                                  params_sds, pspecs)
    with contextlib.nullcontext():
        return _compile_inner(cfg, shape, mesh, wash, mixing_kind, chips,
                              params_sds, pspecs)


def _compile_inner(cfg, shape, mesh, wash, mixing_kind, chips, params_sds, pspecs):
    t0 = time.time()
    if shape.kind == "train" and not wash:
        step = make_train_step(cfg)
        opt_sds = opt_shapes(params_sds, "adamw")
        opt_specs = {"mu": pspecs, "nu": pspecs, "step": P()}
        bspecs = rules.batch_pspecs(cfg, mesh, shape.global_batch)
        specs = input_specs(cfg, shape)
        lowered = jax.jit(
            step,
            in_shardings=(
                rules.named(pspecs, mesh),
                rules.named(opt_specs, mesh),
                rules.named(bspecs, mesh),
            ),
            donate_argnums=(0, 1),
        ).lower(params_sds, opt_sds, specs)

    elif shape.kind == "train" and wash:
        local = mixing_kind.endswith("_local")
        base_kind = mixing_kind[:-6] if local else mixing_kind
        mcfg = MixingConfig(kind=base_kind, base_p=0.05, mode="bucketed")
        stack = lambda t: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((wash,) + x.shape, x.dtype), t
        )
        pop_sds = stack(params_sds)
        opt_sds = stack(opt_shapes(params_sds, "adamw"))
        add_ens = lambda tree: jax.tree_util.tree_map(
            lambda s: P(*(("ens",) + tuple(s))), tree,
            is_leaf=lambda x: isinstance(x, P),
        )
        pop_specs = add_ens(pspecs)
        opt_specs = {"mu": pop_specs, "nu": pop_specs, "step": P("ens")}
        mix_fn = (
            make_shardlocal_mixer(cfg, mcfg, mesh, pop_specs, opt_specs)
            if local else None
        )
        step = make_wash_step(cfg, wash, mcfg, mix_fn=mix_fn)
        per_member = shape.global_batch // wash
        batch_sds = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((wash, per_member) + x.shape[1:], x.dtype),
            input_specs(cfg, dataclasses.replace(shape, global_batch=per_member)),
        )
        bspecs = add_ens(rules.batch_pspecs(cfg, mesh, per_member))
        key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        lowered = jax.jit(
            step,
            in_shardings=(
                rules.named(pop_specs, mesh),
                rules.named(opt_specs, mesh),
                rules.named(bspecs, mesh),
                NamedSharding(mesh, P(None)),
            ),
            donate_argnums=(0, 1),
        ).lower(pop_sds, opt_sds, batch_sds, key_sds)

    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, shape.seq_len)
        bspecs = rules.batch_pspecs(cfg, mesh, shape.global_batch)
        specs = input_specs(cfg, shape)
        lowered = jax.jit(
            step, in_shardings=(rules.named(pspecs, mesh), rules.named(bspecs, mesh))
        ).lower(params_sds, specs)

    else:  # decode
        step = make_serve_step(cfg)
        specs = input_specs(cfg, shape)
        cache_specs = rules.cache_pspecs(specs["cache"], cfg, mesh, shape.global_batch)
        dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        nd = 1
        for a in dax:
            nd *= mesh.shape[a]
        tok_spec = (
            P(dax, None)
            if dax and shape.global_batch % max(nd, 1) == 0
            else P(None, None)
        )
        lowered = jax.jit(
            step,
            in_shardings=(
                rules.named(pspecs, mesh),
                NamedSharding(mesh, tok_spec),
                rules.named(cache_specs, mesh),
                NamedSharding(mesh, P()),
            ),
            donate_argnums=(2,),
        ).lower(params_sds, specs["tokens"], specs["cache"], specs["pos"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    # pod boundary: first mesh axis of the 512-chip meshes is pod/ens
    boundary = chips // 2 if chips == 512 else 0
    stats = hlo_stats.summarize(cost, compiled.as_text(), chips, boundary)
    stats["t_lower_s"] = round(t_lower, 2)
    stats["t_compile_s"] = round(t_compile, 2)

    mem = compiled.memory_analysis()

    def _mem(name):
        try:
            return int(getattr(mem, name, 0) or 0)
        except Exception:
            return 0

    memory = {
        "argument_size": _mem("argument_size_in_bytes"),
        "output_size": _mem("output_size_in_bytes"),
        "temp_size": _mem("temp_size_in_bytes"),
        "generated_code_size": _mem("generated_code_size_in_bytes"),
    }
    return stats, memory


# ---------------------------------------------------------------------------
# planner-only pipeline accounting (no devices, no compile)
# ---------------------------------------------------------------------------


def pipeline_report(arch_id: str, population: int, stages: int,
                    mixing_kind: str = "wash") -> dict:
    """Per-stage WASH comm for ``arch`` on an (ens, data, pipe) mesh.

    Runs the :mod:`repro.core.shardplan` planner on a *fake* mesh object —
    axis names + sizes are all it reads — so full-scale stage budgets
    (kimi 61 layers, internvl 80) come out of a laptop process with no
    devices and no compile.  Asserts the refactor's accounting contract:
    the per-stage volumes sum exactly to the pipeline plan's global, which
    never exceeds the single-stage plan's.
    """
    from types import SimpleNamespace

    from repro.core import shardplan

    cfg = get_arch(arch_id)
    params_sds = params_shapes(cfg)
    lids = infer_layer_ids(params_sds, cfg.num_layers)
    tl = total_layers(cfg.num_layers)
    member_specs = jax.tree_util.tree_map(lambda _: P(), params_sds)
    mcfg = MixingConfig(kind=mixing_kind, base_p=0.05, mode="bucketed")

    mesh = SimpleNamespace(
        axis_names=("ens", "data", "pipe"),
        shape={"ens": population, "data": 1, "pipe": stages},
    )
    staged_specs = rules.stage_member_specs(member_specs, lids, "pipe")
    pplan = shardplan.plan_population_mixing(
        mesh, params_sds, staged_specs, mcfg, lids, tl, population
    )
    per_stage = [
        shardplan.static_stage_mix_comm(pplan, s) for s in range(stages)
    ]
    total = shardplan.static_shard_mix_comm(pplan)

    mesh1 = SimpleNamespace(
        axis_names=("ens", "data"), shape={"ens": population, "data": 1}
    )
    plan1 = shardplan.plan_population_mixing(
        mesh1, params_sds, member_specs, mcfg, lids, tl, population
    )
    single = shardplan.static_shard_mix_comm(plan1)

    assert sum(per_stage) == total, (per_stage, total)
    assert total <= single + 1e-6, (total, single)
    return {
        "arch": arch_id,
        "population": population,
        "stages": stages,
        "mixing": mixing_kind,
        "num_layers": cfg.num_layers,
        "per_stage_scalars": per_stage,
        "total_scalars": total,
        "single_stage_scalars": single,
    }


# ---------------------------------------------------------------------------
# per-pair orchestration
# ---------------------------------------------------------------------------


def lower_pair(arch_id: str, shape_name: str, multi_pod: bool, wash: int = 0,
               mixing_kind: str = "wash", full_unroll: bool = False,
               overrides: dict = None):
    """``overrides``: §Perf hillclimb knobs applied on top of the baseline
    config (e.g. {"attn_impl": "chunked", "remat_blocks": True})."""
    shape = INPUT_SHAPES_BY_NAME[shape_name]
    cfg0 = get_arch(arch_id)
    cfg, note = variant_for(cfg0, shape)
    if cfg is None:
        return {"arch": arch_id, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skip", "note": note}
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    mesh = (
        make_ensemble_mesh(wash, multi_pod=multi_pod)
        if wash
        else make_production_mesh(multi_pod=multi_pod)
    )
    chips = mesh.size
    params_sds = params_shapes(cfg)
    n_params = _count(params_sds)
    n_active = active_params(cfg, params_sds)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)

    if full_unroll:
        full_cfg = dataclasses.replace(cfg, scan_unroll=cfg.num_layers)
        stats, memory = compile_once(full_cfg, shape, mesh, wash, mixing_kind)
        anchors = {"mode": "full_unroll"}
    else:
        # proof compile: full depth, rolled
        stats_full, memory = compile_once(cfg, shape, mesh, wash, mixing_kind)
        # cost anchors: depth-1 / depth-2, unrolled
        s1, _ = compile_once(depth_variant(cfg, 1), shape, mesh, wash, mixing_kind)
        s2, _ = compile_once(depth_variant(cfg, 2), shape, mesh, wash, mixing_kind)
        L = cfg.num_layers
        stats = dict(stats_full)
        for k in _EXTRAP_KEYS:
            v1, v2 = float(s1.get(k, 0.0)), float(s2.get(k, 0.0))
            stats[k] = max(v1 + (L - 1) * (v2 - v1), 0.0)
        # recompute the time terms from the extrapolated primitives so the
        # three terms stay consistent with the byte/flop fields
        coll = sum(stats.get(f"bytes_{c}", 0.0) for c in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute"))
        stats["collective_bytes"] = coll
        stats["compute_s"] = stats["hlo_flops"] / hlo_stats.PEAK_FLOPS
        stats["memory_s"] = stats["hlo_bytes"] / hlo_stats.HBM_BW
        stats["collective_s"] = coll / hlo_stats.ICI_BW
        stats["dominant"] = hlo_stats.dominant_term(stats)
        anchors = {
            "mode": "extrapolated",
            "rolled_full": {k: stats_full.get(k) for k in _EXTRAP_KEYS},
            "depth1": {k: s1.get(k) for k in _EXTRAP_KEYS},
            "depth2": {k: s2.get(k) for k in _EXTRAP_KEYS},
            "t_compile_anchors_s": [s1["t_compile_s"], s2["t_compile_s"]],
        }

    mf = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens
    if wash:
        mf *= 1.0  # population step processes the same global token count

    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": list(mesh.shape.values()),
        "axes": list(mesh.axis_names),
        "multi_pod": multi_pod,
        "wash": wash,
        "mixing": mixing_kind if wash else None,
        "status": "ok",
        "note": note,
        "chips": chips,
        "n_params": n_params,
        "n_active": n_active,
        "tokens": tokens,
        "model_flops": mf,
        **stats,
        **memory,
        "useful_flops_ratio": (
            mf / stats["global_flops"] if stats.get("global_flops") else None
        ),
        "anchors": anchors,
    }
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Multi-pod dry-run: lower + compile every "
                    "(arch x shape x mesh) pair and emit roofline terms",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    ap.add_argument("--arch", default=None,
                    help="architecture name from repro.configs (omit with "
                         "--all to sweep every arch)")
    ap.add_argument("--shape", default=None,
                    help="input shape name: train_4k, prefill_32k, "
                         "decode_32k, long_500k (omit with --all to sweep)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 multi-pod mesh instead of the "
                         "single-pod 16x16")
    ap.add_argument("--wash", type=int, default=0,
                    help="population size (ens axis); 0 = no population, "
                         "plain data/model parallel compile")
    ap.add_argument("--mixing", default="wash",
                    choices=["wash", "wash_opt", "papa", "papa_all",
                             "wash_local", "wash_opt_local"],
                    help="mixing op compiled into the WASH step; *_local "
                         "variants build per-parameter-shard plans "
                         "(core.shardplan)")
    ap.add_argument("--full-unroll", action="store_true",
                    help="unroll all layers instead of the depth-1/depth-2 "
                         "extrapolation (slow; exact for WASH traffic)")
    ap.add_argument("--pp-stages", type=int, default=0,
                    help="planner-only pipeline report: partition --arch's "
                         "WASH plan into this many stages on a fake "
                         "(ens, data, pipe) mesh and print per-stage comm "
                         "(population = --wash, default 4; no devices, no "
                         "compile)")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) pair")
    ap.add_argument("--out-dir", default="benchmarks/dryrun",
                    help="directory for the per-pair JSON records")
    ap.add_argument("--attn-impl", default=None, choices=["naive", "chunked"],
                    help="override cfg.attn_impl for the compile")
    ap.add_argument("--attn-chunk", type=int, default=None,
                    help="override cfg.attn_chunk (kv-chunk size)")
    ap.add_argument("--remat", action="store_true",
                    help="activation-checkpoint each block (training shapes)")
    ap.add_argument("--moe-impl", default=None, choices=["global", "grouped"],
                    help="override cfg.moe_impl for MoE archs")
    ap.add_argument("--hints", action="store_true",
                    help="enable in-model GSPMD sharding constraints")
    ap.add_argument("--tag", default=None, help="suffix for the output file")
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry event stream (per-pair "
                         "lowering spans + final metric snapshots) as "
                         "JSONL here; validate with "
                         "tools/check_metrics_schema.py")
    args = ap.parse_args(argv)

    from repro import obs

    tel = obs.configure(jsonl=args.metrics_out)

    if args.pp_stages:
        if not args.arch:
            ap.error("--pp-stages needs --arch")
        base = args.mixing[:-6] if args.mixing.endswith("_local") else args.mixing
        rec = pipeline_report(args.arch, args.wash or 4, args.pp_stages, base)
        stages_str = " ".join(
            f"s{i}={v:.3e}" for i, v in enumerate(rec["per_stage_scalars"])
        )
        print(f"[pipeline] {rec['arch']} N={rec['population']} "
              f"S={rec['stages']} L={rec['num_layers']}: {stages_str}")
        print(f"[pipeline] total={rec['total_scalars']:.6e} "
              f"(= sum of stages) vs single-stage "
              f"{rec['single_stage_scalars']:.6e}")
        tel.event("dryrun.pipeline_report", arch=rec["arch"],
                  stages=rec["stages"], total_scalars=rec["total_scalars"])
        tel.finalize()
        sys.exit(0)

    overrides = {}
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl
    if args.attn_chunk:
        overrides["attn_chunk"] = args.attn_chunk
    if args.remat:
        overrides["remat_blocks"] = True
    if args.moe_impl:
        overrides["moe_impl"] = args.moe_impl
    if args.hints:
        overrides["shard_hints"] = True

    pairs = []
    if args.all:
        for aid in ARCHS:
            for sh in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
                pairs.append((aid, sh))
    else:
        pairs.append((args.arch, args.shape))

    os.makedirs(args.out_dir, exist_ok=True)
    ok = True
    for aid, sh in pairs:
        tag = f"{aid}_{sh}_{'mp' if args.multi_pod else 'sp'}" + (
            f"_wash{args.wash}_{args.mixing}" if args.wash else ""
        ) + ("_fu" if args.full_unroll else "") + (
            f"_{args.tag}" if args.tag else ""
        )
        path = os.path.join(args.out_dir, tag + ".json")
        if args.all and os.path.exists(path):
            print(f"[skip-cached] {tag}", flush=True)
            continue
        try:
            with tel.span("dryrun.lower_pair", arch=aid, shape=sh):
                rec = lower_pair(aid, sh, args.multi_pod, args.wash,
                                 args.mixing, args.full_unroll,
                                 overrides or None)
            rec["overrides"] = overrides
        except Exception as e:  # noqa
            rec = {
                "arch": aid, "shape": sh, "multi_pod": args.multi_pod,
                "wash": args.wash, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:],
            }
            ok = False
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        if rec["status"] == "ok":
            print(
                f"[ok] {tag}: compute={rec['compute_s']:.3e}s memory={rec['memory_s']:.3e}s "
                f"collective={rec['collective_s']:.3e}s dominant={rec['dominant']} "
                f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)} "
                f"(compile {rec['t_compile_s']}s)", flush=True,
            )
        elif rec["status"] == "skip":
            print(f"[skip] {tag}: {rec['note']}", flush=True)
        else:
            print(f"[ERROR] {tag}: {rec['error']}", flush=True)
    tel.finalize()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
