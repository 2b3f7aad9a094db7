"""Run the main path on a TPU and check every phase.

The path is WASH population training (the fused shard_map engine) →
uniform soup → continuous-batching serving through the paged-KV Pallas
kernel, at Qwen3-4B's published widths with depth and vocabulary cut to
one chip's share (the cuts are printed).  Weights are random from a seed.

    python chip_smoke.py            # one chip: train → soup → serve
    python chip_smoke.py --chips 4  # four chips: WASH and PAPA with one
                                    # member per chip, plus the vmap
                                    # reference loop on device 0

Every check that fails raises, so the process exits non-zero and never
prints the result line.  Without a TPU it exits non-zero at once.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# Tolerances of the checks against the float32 reference (the same
# weights, cast to f32, run by the model's own full-sequence forward under
# ``jax.default_matmul_precision("highest")``).  The system under test
# keeps weights, activations and KV pages in bf16: each rounding is at
# most 2^-9 relative, and four blocks of them stack to a few 1e-3 on the
# ~ln(V) ≈ 10.5 cross entropy and a few 1e-2 on the O(1) logits.
#: |engine loss - f32 loss| / f32 loss at step 0 (one bf16 step is 2^-8).
LOSS_RTOL = 2.0 ** -8
#: reference max logit minus the reference logit of the served token.
#: A served token may differ from the f32 argmax only where two logits sit
#: within the bf16 path's error of each other.
LOGIT_GAP_TOL = 0.25
#: |fused engine loss - vmap loop loss| / loss at step 0: both run the
#: same bf16 forward and differ only in XLA's fusion and reduction order.
ENGINE_RTOL = 2.0 ** -8


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Run sizes; the defaults are the chip run's."""

    population: int = 2
    batch: int = 2              # 4x512 per member exceeds 16 GB (topology compile)
    seq_len: int = 512
    steps: int = 6
    wash_stop: int = 3          # WASH mixes steps [0, 3): both executables run
    requests: int = 8
    max_prompt: int = 512       # prompts of max_prompt/4 .. max_prompt tokens
    max_new: int = 32
    page_size: int = 16
    max_slots: int = 4
    prefill_chunk: int = 128


def smoke_config(num_layers: int = 4):
    """Qwen3-4B at published widths; depth and vocabulary cut to one chip."""
    from repro.configs import get_arch

    base = get_arch("qwen3-4b")
    cfg = dataclasses.replace(base, num_layers=num_layers,
                              vocab_size=base.vocab_size // 4)
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"qk_norm={cfg.qk_norm} rope_theta={cfg.rope_theta} "
          f"dtype={cfg.dtype}", flush=True)
    print(f"cuts: num_layers {base.num_layers} -> {cfg.num_layers}; "
          f"vocab_size {base.vocab_size} -> {cfg.vocab_size} (one chip's "
          f"share of a 4-way vocabulary split)", flush=True)
    return cfg


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _wash(sz: Sizes):
    from repro.core.mixing import MixingConfig

    return MixingConfig(kind="wash", base_p=0.01, mode="bucketed",
                        stop_step=sz.wash_stop, pallas_shuffle=True)


def train(cfg, sz: Sizes, mcfg, *, key, engine="shard_map", mesh=None):
    """One population run.  Returns the result, the step-0 batch of each
    member and each recorded step's host time (after its loss readback)."""
    from repro.configs.base import TrainConfig
    from repro.models import transformer as M
    from repro.train import train_population

    seen = {}
    stamps = {}

    def data_fn(m, step, k):
        b = {"tokens": jax.random.randint(k, (sz.batch, sz.seq_len), 0,
                                          cfg.vocab_size, jnp.int32)}
        if step == 0:
            seen[m] = b
        return b

    def record_fn(step, _population):
        stamps[step] = time.perf_counter()
        return {}

    tcfg = TrainConfig(population=sz.population, optimizer="sgd", lr=0.01,
                       total_steps=sz.steps, batch_size=sz.batch,
                       seq_len=sz.seq_len)
    stamps[-1] = time.perf_counter()
    res = train_population(
        key, lambda k: M.init_params(k, cfg), _loss_fn(cfg), data_fn, tcfg,
        mcfg, cfg.num_layers, record_every=1, record_fn=record_fn,
        engine=engine, mesh=mesh,
    )
    return res, seen, stamps


def _loss_fn(cfg):
    from repro.models import transformer as M

    return lambda p, b: M.loss_fn(p, cfg, b)[0]


def reference_loss(cfg, key, batches) -> float:
    """Mean f32 loss of the members' common initialization (WASH starts
    every member at θ0 = init(key)) on their step-0 batches."""
    from repro.models import transformer as M

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params = _f32(M.init_params(key, cfg))
    with jax.default_matmul_precision("highest"):
        f = jax.jit(lambda p, b: M.loss_fn(p, cfg32, b)[0])
        losses = [float(f(params, batches[m])) for m in sorted(batches)]
    return float(np.mean(losses))


def check_training(cfg, sz: Sizes, mcfg, res, seen, key) -> None:
    from repro.analysis.contracts import replay_comm
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.core.mixing import mixing_due, static_mix_comm
    from repro.train import engine as T

    losses = res.history["loss"]
    print(f"  losses: {losses}", flush=True)
    check(all(np.isfinite(losses)), "every recorded loss is finite")
    traces = T.chunk_trace_count()
    check(traces <= 2, f"fused engine traced {traces} executables (<= 2)")
    member = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), res.population)
    lids = infer_layer_ids(member, cfg.num_layers)
    per_mix = static_mix_comm(member, mcfg, lids, total_layers(cfg.num_layers),
                              sz.population)
    gates = [mixing_due(s, mcfg) for s in range(sz.steps)]
    want = replay_comm(per_mix, gates)
    check(res.comm_scalars == want,
          f"host comm {res.comm_scalars!r} == static_mix_comm {per_mix!r} "
          f"x {sum(gates)} mixed steps")
    ref = reference_loss(cfg, key, seen)
    err = abs(losses[0] - ref) / abs(ref)
    check(err <= LOSS_RTOL,
          f"step-0 loss {losses[0]!r} vs f32 reference {ref!r}: relative "
          f"error {err:.3e} <= {LOSS_RTOL:.3e}")


def _step_times(stamps, steps, stop):
    """(mixing, no-mix) steady step times: record-to-record intervals past
    each executable's first (compiling) step."""
    dt = {s: stamps[s] - stamps[s - 1] for s in range(steps)}
    mix = [dt[s] for s in range(1, stop)]
    nomix = [dt[s] for s in range(stop + 1, steps)]
    return dt, mix, nomix


def serve(soup, cfg, sz: Sizes, *, seed: int = 0, use_pallas=None):
    """Serve the mixed stream through the driver; check completion, the
    compiled kernel, the decode trace count and every emitted token's f32
    reference logit."""
    from repro.launch.serve import mixed_stream
    from repro.models import transformer as M
    from repro.serving import batching
    from repro.serving.driver import RequestDriver

    max_pages = -(-(sz.max_prompt + sz.max_new) // sz.page_size)
    server = batching.ContinuousServer(
        soup, cfg, mode="soup", page_size=sz.page_size,
        max_slots=sz.max_slots, num_pages=sz.max_slots * max_pages + 1,
        max_pages_per_slot=max_pages, use_pallas=use_pallas,
    )
    check(server.use_pallas, "decode attends through the Pallas paged kernel")
    reqs = mixed_stream(cfg, sz.requests, sz.max_prompt, sz.max_new, seed)
    batching.reset_trace_counts()
    t0 = time.perf_counter()
    metrics = RequestDriver(server, prefill_chunk=sz.prefill_chunk).run(reqs)
    wall = time.perf_counter() - t0
    st = server.stats
    served = sum(len(metrics[r.uid].tokens) - len(r.tokens) for r in reqs
                 if metrics[r.uid].tokens is not None)
    print(f"  requests {len(reqs)}, prompts "
          f"{min(len(r.tokens) for r in reqs)}..{max(len(r.tokens) for r in reqs)}"
          f" tokens, tokens served {served}, stream wall {wall:.3f} s "
          f"(compiles included), decode steps {st['decode_steps']}, "
          f"decode traces {batching.decode_trace_count()}, prefill traces "
          f"{batching.prefill_trace_count()}, peak pages "
          f"{st['peak_pages_in_use']}", flush=True)
    check(all(not metrics[r.uid].cancelled and metrics[r.uid].tokens is not None
              and len(metrics[r.uid].tokens) == len(r.tokens) + r.max_new
              for r in reqs),
          f"all {len(reqs)} requests completed with their full budgets")
    check(batching.decode_trace_count() == 1, "one decode trace")

    # f32 reference: every sequence right-padded to one length (causal
    # attention keeps the padding out of every earlier position)
    length = sz.max_prompt + sz.max_new
    toks = np.zeros((len(reqs), length), np.int32)
    for i, r in enumerate(reqs):
        t = metrics[r.uid].tokens
        toks[i, :len(t)] = t
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: M.forward_logits(p, cfg32, {"tokens": t})[0])
        logits = fwd(_f32(soup), jnp.asarray(toks))
    gaps, agree = [], 0
    for i, r in enumerate(reqs):
        S = len(r.tokens)
        rows = np.asarray(logits[i, S - 1:S + r.max_new - 1])
        emitted = toks[i, S:S + r.max_new]
        gaps += list(rows.max(-1) - rows[np.arange(r.max_new), emitted])
        agree += int((rows.argmax(-1) == emitted).sum())
    worst = float(max(gaps))
    print(f"  f32 reference argmax agrees on {agree}/{len(gaps)} positions; "
          f"largest max-logit gap of an emitted token {worst:.4f}", flush=True)
    check(worst <= LOGIT_GAP_TOL,
          f"every emitted token within {LOGIT_GAP_TOL} of the f32 "
          f"reference max logit")


def one_chip(cfg, sz: Sizes, *, seed: int = 0, use_pallas=None) -> None:
    from repro.serving import averaged_params
    from repro.train import engine as T

    key = jax.random.key(seed)
    mcfg = _wash(sz)
    print(f"train: population {sz.population}, batch {sz.batch}x{sz.seq_len}"
          f", {sz.steps} steps, WASH bucketed p={mcfg.base_p} on steps "
          f"[0, {mcfg.stop_step}), Pallas shuffle, SGD, fused engine",
          flush=True)
    mesh = jax.make_mesh((1,), ("ens",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    T.reset_chunk_trace_count()
    res, seen, stamps = train(cfg, sz, mcfg, key=key, mesh=mesh)
    dt, mix, nomix = _step_times(stamps, sz.steps, sz.wash_stop)
    print(f"  first mixing step {dt[0]:.3f} s and first no-mix step "
          f"{dt[sz.wash_stop]:.3f} s (state init and compile included); "
          f"steady steps (loss and consensus readback included): mixing "
          f"{mix}, no-mix {nomix}; traces {T.chunk_trace_count()}",
          flush=True)
    check_training(cfg, sz, mcfg, res, seen, key)

    print("soup + serve: uniform soup, continuous batching, paged KV "
          f"(page size {sz.page_size}, {sz.max_slots} slots, prefill chunk "
          f"{sz.prefill_chunk})", flush=True)
    soup = averaged_params(res)
    del res
    serve(soup, cfg, sz, seed=seed, use_pallas=use_pallas)


def _chunk_report(mesh, cfg, sz: Sizes, mcfg, contract):
    """Compile the fused engine's mixing chunk for ``mesh`` with the
    engine's own arguments, shardings and donation, and check ``contract``
    on its HLO.  The program is the one the engine then runs, so the
    engine's compile of it is a persistent-cache hit."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.analysis import contracts
    from repro.configs.base import TrainConfig
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.models import transformer as M
    from repro.optim import make_optimizer
    from repro.train import engine as T

    tcfg = TrainConfig()
    opt_init, opt_update = make_optimizer(
        "sgd", momentum=tcfg.momentum, weight_decay=tcfg.weight_decay)
    member = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    n = sz.population
    popn = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), member)
    opt = jax.eval_shape(jax.vmap(opt_init), popn)
    ens = lambda tree: jax.tree_util.tree_map(lambda _: P("ens"), tree)  # noqa: E731
    fn = T.make_fused_chunk_fn(
        mesh, mcfg, infer_layer_ids(member, cfg.num_layers),
        total_layers(cfg.num_layers), opt_update, _loss_fn(cfg), ens(popn),
        ens(opt), {"tokens": P(None, "ens")}, use_pallas=mcfg.pallas_shuffle,
    )

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    kd = jax.random.key_data(jax.random.key(0))
    args = (
        jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype, P("ens")), popn),
        jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype, P("ens")), opt),
        {"tokens": sds((1, n, sz.batch, sz.seq_len), jnp.int32,
                       P(None, "ens"))},
        sds((1,), jnp.float32, P()), sds((1,) + kd.shape, kd.dtype, P()),
        sds((1,), jnp.float32, P()), sds((), jnp.int32, P()),
    )
    report = contracts.lower_and_check(fn, args, contract)
    member_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(member))
    print(f"  {contract.name}: collectives {report.counts}, bytes "
          f"{report.bytes}, member params {member_bytes} bytes", flush=True)
    return report, member_bytes


def four_chips(cfg, sz: Sizes, *, seed: int = 0) -> None:
    from repro.analysis.contracts import Contract
    from repro.core.mixing import MixingConfig
    from repro.launch.mesh import make_host_ensemble_mesh
    from repro.train import engine as T

    key = jax.random.key(seed)
    mesh = make_host_ensemble_mesh(sz.population)
    devices = set(mesh.devices.flat)
    check(mesh.shape["ens"] == sz.population == len(devices),
          f"(ens={sz.population},) mesh, one member per chip")
    runs = {
        "wash": (_wash(sz), Contract(
            name="wash_chunk", require_collectives=("collective-permute",),
            donate_argnums=(0, 1))),
        "papa": (MixingConfig(kind="papa", papa_every=sz.steps - 1,
                              papa_alpha=0.99), Contract(
            name="papa_chunk", require_collectives=("all-reduce",),
            forbid_collectives=("collective-permute",),
            donate_argnums=(0, 1))),
    }
    for name, (mcfg, contract) in runs.items():
        print(f"{name}: population {sz.population} on {sorted(d.id for d in devices)}"
              f", {sz.steps} steps", flush=True)
        report, member_bytes = _chunk_report(mesh, cfg, sz, mcfg, contract)
        reduced = report.bytes.get("all-reduce", 0)
        if name == "wash":
            # the one all-reduce left is the f32 scalar loss mean
            check(reduced <= 4, f"WASH chunk all-reduces {reduced} bytes: "
                                "the loss only, no parameter")
        else:
            check(reduced >= member_bytes,
                  f"PAPA chunk all-reduces {reduced} bytes >= one member's "
                  f"{member_bytes} parameter bytes")
        T.reset_chunk_trace_count()
        res, _, _ = train(cfg, sz, mcfg, key=key, mesh=mesh)
        losses = res.history["loss"]
        print(f"  losses {losses}, traces {T.chunk_trace_count()}, comm "
              f"{res.comm_scalars!r}", flush=True)
        check(all(np.isfinite(losses)), "every recorded loss is finite")
        check(T.chunk_trace_count() <= 2, "fused engine traced <= 2 executables")
        placed = all(
            {s.device for s in leaf.addressable_shards} == devices
            and all(s.data.shape[0] == 1 for s in leaf.addressable_shards)
            for leaf in jax.tree_util.tree_leaves(res.population))
        check(placed, "every population leaf holds exactly one member on "
                      "each chip")
        del res

    # the vmap reference loop holds all four members on device 0, so it
    # runs one block deep; the fused engine runs the same config on the
    # mesh.  The step-0 loss is taken before any mixing, so neither run
    # mixes (mixing would only add a compile).
    small = dataclasses.replace(cfg, num_layers=1)
    one_step = dataclasses.replace(sz, steps=1)
    mcfg = MixingConfig(kind="none")
    print(f"reference: {small.num_layers} layer, one step, fused engine on "
          f"the mesh vs the vmap loop on device 0", flush=True)
    fused, _, _ = train(small, one_step, mcfg, key=key, mesh=mesh)
    fused_loss = fused.history["loss"][0]
    del fused
    ref, _, _ = train(small, one_step, mcfg, key=key, engine="vmap")
    ref_loss = ref.history["loss"][0]
    del ref
    err = abs(fused_loss - ref_loss) / abs(ref_loss)
    check(err <= ENGINE_RTOL,
          f"step-0 loss fused {fused_loss!r} vs vmap {ref_loss!r}: relative "
          f"error {err:.3e} <= {ENGINE_RTOL:.3e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train, soup and serve on one chip; 4: WASH and "
                         "PAPA with one member per chip")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"devices: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 "this check runs on the chip only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, JAX found {len(devices)}")

    from repro.core.compat import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cache = {"cache_hits": 0, "cache_misses": 0}

    def count(event: str, **_kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in cache:
            cache[name] += 1

    jax.monitoring.register_event_listener(count)
    check(not resolve_interpret(None), "Pallas kernels compile for the chip")
    cfg = smoke_config()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(cfg, Sizes(population=4, batch=4, steps=3))
    else:
        one_chip(cfg, Sizes())
    print(f"wall {time.perf_counter() - t0:.3f} s; compile cache {cache}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
