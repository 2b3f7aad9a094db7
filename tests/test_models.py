"""Model zoo behaviour: decode == forward, ring caches, MoE dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import moe as MOE
from repro.models import transformer as M

KEY = jax.random.key(1)

FAMILIES = {
    "dense": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                         d_ff=128, vocab_size=100, dtype="float32"),
    "qknorm_bias": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                               d_ff=128, vocab_size=100, qk_norm=True, qkv_bias=True,
                               dtype="float32"),
    "window": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=100, window=8, dtype="float32"),
    "mla_moe": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                           d_ff=128, vocab_size=100, mla=True, kv_lora_rank=32,
                           qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, moe=True,
                           n_routed_experts=4, n_shared_experts=1, top_k=2,
                           moe_d_ff=32, capacity_factor=8.0, dtype="float32"),
    "rwkv6": ModelConfig(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                         d_ff=128, vocab_size=100, block_kind="rwkv6",
                         rwkv_head_dim=32, dtype="float32"),
    "hybrid": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=100, block_kind="hybrid", window=8,
                          ssm_state=8, dtype="float32"),
    "whisper": ModelConfig(num_layers=2, encoder_layers=2, d_model=64, num_heads=4,
                           num_kv_heads=4, d_ff=128, vocab_size=100,
                           pos_kind="learned", max_position=64, num_frames=8,
                           frontend="audio", dtype="float32"),
    "vlm": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                       d_ff=128, vocab_size=100, frontend="vision", num_patches=4,
                       dtype="float32"),
}


def _batches(cfg, S, key=KEY):
    toks = jax.random.randint(key, (2, S + 1), 0, cfg.vocab_size)
    full = {"tokens": toks}
    pre = {"tokens": toks[:, :S]}
    if cfg.frontend == "vision":
        pat = jax.random.normal(key, (2, cfg.num_patches, cfg.d_model))
        full["patches"] = pat
        pre["patches"] = pat
    if cfg.is_encdec:
        fr = jax.random.normal(key, (2, cfg.num_frames, cfg.d_model))
        full["frames"] = fr
        pre["frames"] = fr
    return toks, full, pre


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_decode_matches_forward(name):
    cfg = FAMILIES[name]
    S = 12
    toks, full, pre = _batches(cfg, S)
    params = M.init_params(KEY, cfg)
    logits_full, _ = M.forward_logits(params, cfg, full)
    prefix = cfg.num_patches if cfg.frontend == "vision" else 0
    _, cache = M.prefill(params, cfg, pre, capacity=prefix + S + 2)
    dec, _ = M.decode_step(params, cfg, toks[:, S : S + 1], cache, prefix + S)
    np.testing.assert_allclose(
        np.asarray(logits_full[:, S]), np.asarray(dec[:, 0]), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_train_loss_finite_and_shapes(name):
    cfg = FAMILIES[name]
    _, full, _ = _batches(cfg, 12)
    params = M.init_params(KEY, cfg)
    loss, metrics = M.loss_fn(params, cfg, full)
    assert jnp.isfinite(loss)
    logits, _ = M.forward_logits(params, cfg, full)
    assert logits.shape == (2, 13, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits))


def test_sliding_window_ring_cache_wraps():
    """Decode far beyond the window: ring cache must stay exact."""
    cfg = FAMILIES["window"]  # window=8
    S_total = 30
    toks = jax.random.randint(KEY, (1, S_total), 0, cfg.vocab_size)
    params = M.init_params(KEY, cfg)
    full, _ = M.forward_logits(params, cfg, {"tokens": toks})

    # prefill 4 tokens, then decode one-by-one to the end
    _, cache = M.prefill(params, cfg, {"tokens": toks[:, :4]}, capacity=S_total)
    outs = []
    for pos in range(4, S_total):
        lg, cache = M.decode_step(params, cfg, toks[:, pos : pos + 1], cache, pos)
        outs.append(lg[:, 0])
    # compare the last decode logits (prediction after consuming token S-1)
    np.testing.assert_allclose(
        np.asarray(full[:, -1]), np.asarray(outs[-1]), rtol=2e-3, atol=2e-3
    )


def test_moe_dispatch_matches_dense_reference():
    cfg = ModelConfig(d_model=16, moe=True, n_routed_experts=4, n_shared_experts=0,
                      top_k=2, moe_d_ff=8, capacity_factor=8.0, dtype="float32")
    p = MOE.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 7, 16))
    out, aux = MOE.moe_apply(p, cfg, x)
    xf = x.reshape(-1, 16)
    probs = jax.nn.softmax(xf @ p["router"], -1)
    tw, ti = jax.lax.top_k(probs, 2)
    tw = tw / tw.sum(-1, keepdims=True)
    ref = jnp.zeros_like(xf)
    for e in range(4):
        h = jax.nn.silu(xf @ p["experts"]["w1"][e]) * (xf @ p["experts"]["w3"][e])
        oe = h @ p["experts"]["w2"][e]
        w_e = jnp.where(ti == e, tw, 0.0).sum(-1)
        ref = ref + oe * w_e[:, None]
    np.testing.assert_allclose(
        np.asarray(out.reshape(-1, 16)), np.asarray(ref), rtol=1e-4, atol=1e-5
    )
    assert float(aux) > 0.0


def test_moe_capacity_drops_overflow():
    """The capacity dispatch, which runs where sharding hints are active
    (a mesh with an expert axis), drops what overflows its buffers."""
    from repro.sharding import hints

    cfg = ModelConfig(d_model=16, moe=True, n_routed_experts=4, n_shared_experts=0,
                      top_k=2, moe_d_ff=8, capacity_factor=0.01, dtype="float32")
    p = MOE.moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (4, 64, 16))
    mesh = jax.make_mesh((1,), ("model",), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,))
    with jax.set_mesh(mesh), hints.use_hints((), "model"):
        out, _ = MOE.moe_apply(p, cfg, x)  # almost everything dropped
    assert jnp.all(jnp.isfinite(out))
    # with capacity ~0 most outputs are zero (residual-only)
    frac_zero = float(jnp.mean(jnp.all(out == 0.0, axis=-1)))
    assert frac_zero > 0.5
