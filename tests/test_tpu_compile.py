"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles a kernel at real widths
(Qwen3-4B: 32 query heads, 8 kv heads, head_dim 128, d_ff 9728) for the
first chip of a ``v5e:2x2`` topology, which raises what the chip's
compiler would raise — block shapes off the (sublane, lane) tiling, too
much VMEM — and which interpret mode on the CPU cannot see.  The topology
is described inside a fixture only, so each test worker that imports this
file collects the same tests and only the worker that runs them loads the
TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.wash_shuffle import bucketed_shuffle_pallas

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
