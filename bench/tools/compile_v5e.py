"""Compile a serving cell's programs at real size for a described v5e chip
(no chip needed) and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py \
        qwen1.5-4b.serve.longdoc [--slots 32]

Compiles the continuous decode step and the longest prompt-chunk program
with the cell's pool, slots (or ``--slots``) and page table, the weights
and pools passed as shapes on one described chip, and prints
``memory_analysis()``.  The weights' shapes and the program's
configuration come from the architecture module the cell's configuration
names; the pools' from the program's own ``paged_pools_init``.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generate, harness as H  # noqa: E402
from bench import system  # noqa: E402,F401  (puts the program on the path)


def main(name: str, slots: int = None) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import paged_attention
    from repro.models import layers as L
    from repro.serving import batching

    # the kernel picks interpret mode, and the runtime no donation, on a
    # CPU backend: compile both as they are on the chip
    paged_attention.resolve_interpret = lambda _: False
    batching.donate_argnums = lambda argnums: argnums
    jax.config.update("jax_enable_compilation_cache", False)
    cell = H.find_cell(name)
    A = cell.architecture
    a = A.Arch.from_config(cell.config)
    cfg = A.program_config(a, cell.entry["config"])
    w = cell.workload
    ps, C = w["page_size"], w["prefill_chunk"]
    B = slots or w["max_slots"]
    longest = max(p + o for p, o in generate.size_set(cell.traffic))
    mp = -(-longest // ps)
    P = B * mp + 1
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(lambda k: A.init_weights(k, a),
                                    jax.random.key(0)))
    pools = on_chip(jax.eval_shape(
        lambda: L.paged_pools_init(cfg, P, ps, cfg.num_layers)))
    key_t = jax.eval_shape(lambda: jax.random.key(0))
    keys_t = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))
    sds = lambda s, dtype: jax.ShapeDtypeStruct(s, dtype, sharding=chip)  # noqa: E731
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    pool_gb = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(pools)) / 1e9
    print(f"{name}: slots {B}, pages/slot {mp}, pool pages {P}, pools "
          f"{ {k: v.shape for k, v in pools.items()} } {pool_gb:.2f} GB",
          flush=True)

    decode = batching._programs(cfg, False, (B, mp, ps, P), True, True)
    compiled = decode.lower(
        params, pools["k"], pools["v"], i32(B), i32(B), i32(B), i32(B),
        sds((B,), jnp.bool_), i32(B, mp), sds(keys_t.shape, keys_t.dtype),
        sds((), jnp.float32)).compile()
    print(f"decode step: {compiled.memory_analysis()}", flush=True)

    chunk = batching._chunk_program(cfg, False, C, mp, ps, P, True)
    compiled = chunk.lower(
        params, None, pools["k"], pools["v"], None, None, i32(C), i32(),
        i32(mp), sds(key_t.shape, key_t.dtype), sds((), jnp.float32)
    ).compile()
    print(f"prompt chunk {C}: {compiled.memory_analysis()}", flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("workload")
    ap.add_argument("--slots", type=int,
                    help="slots to compile for (default: the cell's)")
    args = ap.parse_args()
    main(args.workload, args.slots)
