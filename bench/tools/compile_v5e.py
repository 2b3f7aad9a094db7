"""Compile a serving cell's programs at real size for a described v5e chip
(no chip needed) and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu python3 bench/tools/compile_v5e.py qwen1.5-4b.serve.longdoc

Compiles the continuous decode step and the longest prompt-chunk program
with the cell's pool, slots and page table, the weights and pools passed
as shapes on one described chip, and prints ``memory_analysis()``.
"""

from __future__ import annotations

import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generate, harness as H, reference as R  # noqa: E402
from bench.system import program_config  # noqa: E402


def main(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import paged_attention
    from repro.serving import batching

    # the kernel picks interpret mode, and the runtime no donation, on a
    # CPU backend: compile both as they are on the chip
    paged_attention.resolve_interpret = lambda _: False
    batching.donate_argnums = lambda argnums: argnums
    jax.config.update("jax_enable_compilation_cache", False)
    cell = H.find_cell(name)
    a = R.Arch.from_config(cell.config)
    cfg = program_config(a, cell.entry["config"])
    w = cell.workload
    ps, B, C = w["page_size"], w["max_slots"], w["prefill_chunk"]
    longest = max(p + o for p, o in generate.size_set(cell.traffic))
    mp = -(-longest // ps)
    P = B * mp + 1
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: R.init_weights(k, a), jax.random.key(0)))
    pool = sds((a.num_layers, P, ps, a.num_kv_heads, a.head_dim), jnp.bfloat16)
    key_t = jax.eval_shape(lambda: jax.random.key(0))
    keys_t = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    pool_gb = 2 * pool.size * 2 / 1e9
    print(f"{name}: slots {B}, pages/slot {mp}, pool pages {P}, "
          f"pools {pool_gb:.2f} GB", flush=True)

    decode = batching._programs(cfg, False, (B, mp, ps, P), True, True)
    compiled = decode.lower(
        params, pool, pool, i32(B), i32(B), i32(B), i32(B),
        sds((B,), jnp.bool_), i32(B, mp), sds(keys_t.shape, keys_t.dtype),
        sds((), jnp.float32)).compile()
    print(f"decode step: {compiled.memory_analysis()}", flush=True)

    chunk = batching._chunk_program(cfg, False, C, mp, ps, P, True)
    compiled = chunk.lower(
        params, None, pool, pool, None, None, i32(C), i32(), i32(mp),
        sds(key_t.shape, key_t.dtype), sds((), jnp.float32)).compile()
    print(f"prompt chunk {C}: {compiled.memory_analysis()}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
