"""Per-assigned-architecture smoke tests (deliverable f).

Each of the 10 architectures instantiates a REDUCED variant of the same
family (2 layers, d_model<=256, <=4 experts) and runs one forward + one
SGD train step on CPU, asserting output shapes and the absence of NaNs.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.launch.specs import concrete_batch
from repro.models import transformer as M
from repro.optim import make_optimizer

KEY = jax.random.key(0)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_forward_and_train_step(arch_id):
    cfg = get_arch(arch_id).reduced()
    B, S = 2, 16
    params = M.init_params(KEY, cfg)
    batch = concrete_batch(cfg, KEY, B, S)

    logits, aux = M.forward_logits(params, cfg, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits))), f"{arch_id}: NaN logits"

    opt_init, opt_update = make_optimizer("sgd")
    opt = opt_init(params)

    def lf(p):
        loss, _ = M.loss_fn(p, cfg, batch)
        return loss

    loss0, grads = jax.value_and_grad(lf)(params)
    params2, opt = opt_update(params, grads, opt, 0.1)
    loss1, _ = jax.value_and_grad(lf)(params2)
    assert jnp.isfinite(loss0) and jnp.isfinite(loss1), arch_id
    # one SGD step on the same batch should not increase loss much
    assert float(loss1) < float(loss0) + 0.5, (arch_id, float(loss0), float(loss1))
    for leaf in jax.tree_util.tree_leaves(params2):
        assert bool(jnp.all(jnp.isfinite(leaf))), f"{arch_id}: NaN params"


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_reduced_decode_step(arch_id):
    cfg = get_arch(arch_id).reduced()
    B, S = 2, 8
    params = M.init_params(KEY, cfg)
    batch = concrete_batch(cfg, KEY, B, S)
    prefix = cfg.num_patches if cfg.frontend == "vision" else 0
    logits, cache = M.prefill(params, cfg, batch, capacity=prefix + S + 2)
    assert logits.shape == (B, 1, cfg.vocab_size)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, cache2 = M.decode_step(params, cfg, tok, cache, prefix + S)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2))), arch_id


def test_exact_assigned_configs():
    """The full (non-reduced) configs carry the exact assigned dimensions."""
    spec = {
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "rwkv6-3b": (32, 2560, 40, 40, 8960, 65536),
        "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
    }
    for aid, (L, D, H, KV, F, V) in spec.items():
        c = get_arch(aid)
        assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
                c.d_ff, c.vocab_size) == (L, D, H, KV, F, V), aid
    # family-specific invariants
    ds = get_arch("deepseek-v2-lite-16b")
    assert ds.mla and ds.kv_lora_rank == 512 and ds.top_k == 6
    assert ds.n_routed_experts == 64 and ds.n_shared_experts == 2
    assert ds.moe_d_ff == 1408 and ds.first_dense_layers == 1
    assert not ds.norm_topk_prob and ds.norm_eps == 1e-6
    assert ds.rope_scaling.factor == 40 and ds.rope_scaling.mscale_all_dim == 0.707
    kimi = get_arch("kimi-k2-1t-a32b")
    assert kimi.n_routed_experts == 384 and kimi.top_k == 8
    assert get_arch("qwen3-4b").qk_norm
    assert get_arch("qwen1.5-4b").qkv_bias
    assert get_arch("rwkv6-3b").block_kind == "rwkv6"
    assert get_arch("hymba-1.5b").block_kind == "hybrid"
    assert get_arch("hymba-1.5b").ssm_state == 16
    assert get_arch("whisper-medium").encoder_layers == 24
    assert get_arch("internvl2-76b").frontend == "vision"
