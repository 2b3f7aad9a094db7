"""Compile the main path's Pallas kernels and serving programs for a
described TPU v5e.

Nothing runs: each test lowers and compiles a kernel at real widths
(Qwen3-4B: 32 query heads, 8 kv heads, head_dim 128, d_ff 9728) for the
first chip of a ``v5e:2x2`` topology, which raises what the chip's
compiler would raise — block shapes off the (sublane, lane) tiling, too
much VMEM — and which interpret mode on the CPU cannot see.  The serving
programs compile at Qwen1.5-4B widths and are held to the chip's memory
plan: they update the donated KV pools in place.  The topology is
described inside a fixture only, so each test worker that imports this
file collects the same tests and only the worker that runs them loads the
TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import ModelConfig
from repro.kernels import paged_attention
from repro.kernels.paged_attention import (latent_attention_pallas,
                                           paged_attention_pallas)
from repro.kernels.wash_shuffle import bucketed_shuffle_pallas
from repro.models import layers as L
from repro.models import transformer as M
from repro.serving import batching

H, KV, HD = 32, 8, 128
D_MODEL, D_FF = 2560, 9728
SLOTS, PAGES, PAGE_SIZE, MAX_PAGES = 8, 256, 16, 34


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_attention_compiles_for_v5e(one_chip, kv_dtype):
    pool = ((PAGES, PAGE_SIZE, KV, HD), jnp.dtype(kv_dtype))
    shapes = [((SLOTS, H, HD), jnp.bfloat16), pool, pool,
              ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32)]
    if kv_dtype == "int8":
        shapes += [((PAGES,), jnp.float32)] * 2

        def fn(q, k, v, pt, lengths, ks, vs):
            return paged_attention_pallas(q, k, v, pt, lengths, k_scale=ks,
                                          v_scale=vs, interpret=False)
    else:
        def fn(q, k, v, pt, lengths):
            return paged_attention_pallas(q, k, v, pt, lengths,
                                          interpret=False)
    _compile(fn, one_chip, *shapes)


def test_bucketed_shuffle_compiles_for_v5e(one_chip):
    """One d_model x d_ff MLP leaf of a two-member population."""
    d = D_MODEL * D_FF
    compiled = _compile(
        lambda x, idx: bucketed_shuffle_pallas(x, idx, interpret=False),
        one_chip, ((2, d), jnp.bfloat16), ((2, d // 100), jnp.int32))
    mem = compiled.memory_analysis()
    # in, out and the per-coordinate shift map: a byte per coordinate,
    # not an (N, D) int32 permutation
    assert mem.temp_size_in_bytes < 2 * d


# Qwen1.5-4B widths (MHA, 20 heads of 128, qkv bias), 4 layers.  The
# vocabulary is cut to 4,096 rows: in ensemble mode the member-vmapped
# embedding gather relays the table out, a vocabulary-sized copy that is
# not the pool's and that this test does not measure.
SERVE_CFG = ModelConfig(
    name="qwen1.5-4b-widths", family="dense", num_layers=4, d_model=2560,
    num_heads=20, num_kv_heads=20, head_dim=128, d_ff=6912,
    vocab_size=4096, qkv_bias=True, tie_embeddings=False, dtype="bfloat16")
# 32 slots of up to 1,024 tokens over 2,049 pages of 16: one layer's K
# pool (168 MB in bf16) dwarfs a step's activations
SERVE_SLOTS, SERVE_PAGES, SERVE_MAX_PAGES, CHUNK = 32, 2049, 64, 512


@pytest.fixture
def chip_programs(monkeypatch):
    """Build the serving programs as they are on the chip: the kernel
    compiled (not interpreted) and the pools donated."""
    monkeypatch.setattr(paged_attention, "resolve_interpret", lambda _: False)
    monkeypatch.setattr(batching, "donate_argnums", lambda argnums: argnums)
    batching.clear_executable_cache()
    yield
    batching.clear_executable_cache()


@pytest.mark.parametrize("program", ["decode", "prompt_chunk"])
@pytest.mark.parametrize("mode,kv_dtype", [
    ("soup", None), ("soup", "int8"), ("ensemble", None)])
def test_serving_programs_update_the_pools_in_place_on_v5e(
        one_chip, chip_programs, program, mode, kv_dtype):
    """The decode step and a 512-token prompt chunk need less scratch
    memory than one layer's K pool: they scatter their rows into the
    donated pools and read the kernel's view of them, with no per-layer
    slice, relayout or write-back of a pool."""
    cfg, B, mp, P, ps = (SERVE_CFG, SERVE_SLOTS, SERVE_MAX_PAGES,
                         SERVE_PAGES, 16)
    ensemble = mode == "ensemble"
    members = 2 if ensemble else 1

    def on_chip(tree, lead=()):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype=jnp.int32):
        return on_chip(jax.ShapeDtypeStruct(shape, dtype))

    # ensemble programs hold every member's weights and pools
    lead = (members,) if ensemble else ()
    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg)), lead)
    pools = on_chip(jax.eval_shape(
        lambda: L.paged_pools_init(cfg, P, ps, cfg.num_layers,
                                   kv_dtype=kv_dtype)), lead)
    key = jax.eval_shape(lambda: jax.random.key(0))
    temperature = arg((), jnp.float32)
    if program == "decode":
        fn = batching._programs(cfg, ensemble, (B, mp, ps, P), True, True,
                                kv_dtype)
        lowered = fn.lower(params, pools["k"], pools["v"], arg((B,)),
                           arg((B,)), arg((B,)), arg((B,)),
                           arg((B,), jnp.bool_), arg((B, mp)),
                           arg((B,), key.dtype), temperature)
    else:
        fn = batching._chunk_program(cfg, ensemble, CHUNK, mp, ps, P, True,
                                     kv_dtype)
        lowered = fn.lower(params, None, pools["k"], pools["v"], None, None,
                           arg((CHUNK,)), arg(()), arg((mp,)),
                           arg((), key.dtype), temperature)
    compiled = lowered.compile()
    if program == "decode":  # the attend is the compiled Pallas kernel
        assert "tpu_custom_call" in compiled.as_text()
    k_pool = jax.tree_util.tree_leaves(pools["k"])[0]
    layer_k_bytes = k_pool.size * k_pool.dtype.itemsize // (
        members * cfg.num_layers)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_k_bytes, (
        f"{program} needs {mem.temp_size_in_bytes / 1e9:.2f} GB of "
        f"temporaries beside one layer's K pool of {layer_k_bytes / 1e9:.2f}"
        " GB: a layer's pool is copied")


# DeepSeek-V2-Lite widths (MLA: latent 512 + rope key 64 shared by 16
# heads; 16 of 64 experts of 1408 held, 2 shared), one dense and one
# expert layer, the vocabulary cut as above; pages of 64 positions
MLA_CFG = ModelConfig(
    name="deepseek-v2-lite-widths", family="moe", num_layers=2,
    d_model=2048, num_heads=16, num_kv_heads=16, d_ff=10944,
    vocab_size=4096, mla=True, kv_lora_rank=512, qk_nope_dim=128,
    qk_rope_dim=64, v_head_dim=128, moe=True, n_routed_experts=16,
    router_experts=64, n_shared_experts=2, top_k=6, moe_d_ff=1408,
    first_dense_layers=1, norm_topk_prob=False, dtype="bfloat16")
MLA_PAGE_SIZE, MLA_MAX_PAGES = 64, 64


def test_latent_attention_compiles_for_v5e(one_chip):
    """The latent kernel at DeepSeek-V2-Lite widths: 16 heads, rows of
    576, pages of 64 positions (two a row)."""
    W = 576
    shapes = [((SLOTS, 16, W), jnp.float32),
              ((PAGES, MLA_PAGE_SIZE // 2, 2 * W), jnp.bfloat16),
              ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32)]
    _compile(lambda q, pool, pt, lengths: latent_attention_pallas(
        q, pool, pt, lengths, scale=0.11, interpret=False), one_chip,
        *shapes)


@pytest.mark.parametrize("program", ["decode", "prompt_chunk"])
def test_latent_serving_programs_update_the_pool_in_place_on_v5e(
        one_chip, chip_programs, program):
    """The MLA programs need less scratch memory than one layer's latent
    pool: the pool keeps the chip's default layout through them (a
    576-wide minor dimension would not: the chip lays the page axis
    minor, and every program would relay the pool out and back)."""
    cfg, B, mp, ps = MLA_CFG, SERVE_SLOTS, MLA_MAX_PAGES, MLA_PAGE_SIZE
    P = B * mp + 1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arg(shape, dtype=jnp.int32):
        return on_chip(jax.ShapeDtypeStruct(shape, dtype))

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg)))
    pools = on_chip(jax.eval_shape(
        lambda: L.paged_pools_init(cfg, P, ps, cfg.num_layers)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    temperature = arg((), jnp.float32)
    if program == "decode":
        fn = batching._programs(cfg, False, (B, mp, ps, P), True, True)
        lowered = fn.lower(params, pools["latent"], arg((B,)), arg((B,)),
                           arg((B,)), arg((B,)), arg((B,), jnp.bool_),
                           arg((B, mp)),
                           arg((B,), key.dtype), temperature)
    else:
        fn = batching._chunk_program(cfg, False, CHUNK, mp, ps, P, True)
        lowered = fn.lower(params, None, pools["latent"], None,
                           arg((CHUNK,)), arg(()), arg((mp,)),
                           arg((), key.dtype), temperature)
    compiled = lowered.compile()
    if program == "decode":
        assert "tpu_custom_call" in compiled.as_text()
    pool = pools["latent"]
    layer_bytes = pool.size * pool.dtype.itemsize // cfg.num_layers
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, (
        f"{program} needs {mem.temp_size_in_bytes / 1e9:.2f} GB of "
        f"temporaries beside one layer's latent pool of "
        f"{layer_bytes / 1e9:.2f} GB: the pool is copied")
