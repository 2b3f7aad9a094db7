"""DeepSeek-V2 on the program's paths: YaRN rope, the latent paged pool
and its decode kernel, the held experts' drop-free layer, the leading
dense stack, and the paged runtime serving it."""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import ModelConfig, Yarn
from repro.core.layer_index import infer_layer_ids
from repro.kernels.paged_attention import latent_attention_pallas
from repro.kernels.ref import latent_attention_ref
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import transformer as M
from repro.serving import batching

ROOT = pathlib.Path(__file__).resolve().parents[1]
KEY = jax.random.key(7)

#: a tiny DeepSeek-V2: one dense and two expert layers, 4 of 8 experts held
#: (the second share), yarn over an original context of 16 positions
CFG = ModelConfig(
    name="tiny-dsv2", family="moe", num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, d_ff=96, vocab_size=128, mla=True, kv_lora_rank=32,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, moe=True,
    n_routed_experts=4, router_experts=8, expert_offset=4,
    n_shared_experts=2, top_k=3, moe_d_ff=24, first_dense_layers=1,
    norm_topk_prob=False, router_aux_coef=0.0,
    rope_scaling=Yarn(factor=4.0, original_max_position=16, mscale=0.707,
                      mscale_all_dim=0.707),
    norm_eps=1e-6, dtype="float32")


def _reference():
    """The benchmark's float32 reference of the same architecture, which
    imports nothing of the program."""
    sys.path.insert(0, str(ROOT))
    from bench import harness as H

    A = H.architecture("deepseek_v2")
    a = A.Arch(
        num_layers=3, dense_layers=1, d_model=64, num_heads=4,
        kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
        d_ff=96, num_experts=4, router_experts=8, first_expert=4,
        shared_experts=2, top_k=3, d_expert=24, norm_topk_prob=False,
        vocab_size=128, rope_theta=1e4, yarn_factor=4.0, yarn_original=16,
        yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707, max_position=32768, norm_eps=1e-6,
        dtype="float32")
    return A, a


def test_yarn_frequencies_follow_the_published_rule():
    """DeepSeek-V2-Lite's rope: dim 64, base 1e4, factor 40 over 4096
    positions: pairs below 10 keep their frequency, pairs from 23 on are
    divided by 40, a linear ramp between; cos and sin keep their scale
    (mscale / mscale_all_dim = 1) and the softmax scale is 192^-0.5 m^2."""
    ds = get_arch("deepseek-v2-lite-16b")
    got = np.asarray(L.rope_freqs(64, ds.rope_theta, ds.rope_scaling))
    f = 1e4 ** (-np.arange(0, 64, 2) / 64)
    r = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)
    # float32 powers against float64 ones
    np.testing.assert_allclose(got, f / 40 * r + f * (1 - r), rtol=1e-6)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert abs(L.mla_scale(ds) - 192 ** -0.5 * m * m) < 1e-9
    x = jnp.ones((1, 2, 1, 64))
    y = L.apply_rope(x, jnp.arange(2), ds.rope_theta, ds.rope_scaling,
                     interleaved=True)
    # position 0 is not rotated, and the cos/sin scale is 1
    np.testing.assert_allclose(np.asarray(y[0, 0]), 1.0, rtol=1e-6)


def test_dense_and_expert_stacks_take_their_wash_depths():
    params = jax.eval_shape(lambda k: M.init_params(k, CFG), KEY)
    ids = infer_layer_ids(params, CFG.num_layers)
    for leaf in jax.tree_util.tree_leaves(ids["dense_blocks"]):
        assert list(leaf) == [1]
    for leaf in jax.tree_util.tree_leaves(ids["blocks"]):
        assert list(leaf) == [2, 3]
    assert ids["embed"]["tok"] == 0 and ids["lm_head"]["w"] == 4
    # the real cut: the dense layer at depth 1, the expert layers 2-5
    real = dataclasses.replace(get_arch("deepseek-v2-lite-16b"), num_layers=5)
    ids = infer_layer_ids(
        jax.eval_shape(lambda k: M.init_params(k, real), KEY), 5)
    assert list(ids["dense_blocks"]["attn"]["wq"]) == [1]
    assert list(ids["blocks"]["mlp"]["router"]) == [2, 3, 4, 5]


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_prefill_then_paged_decode_match_the_reference(use_pallas):
    """Prompt chunks over the latent pages, then paged decode steps, give
    the reference's logits at every position (float32 both sides: only
    rounding separates the absorbed latent attend from the reference's
    expanded one)."""
    A, a = _reference()
    params = M.init_params(KEY, CFG)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, 128, 23), jnp.int32)
    ref = np.asarray(A.logits_at(params, tokens, jnp.arange(23), a))
    pools = L.paged_pools_init(CFG, num_pages=12, page_size=4,
                               num_layers=CFG.num_layers)
    table = jnp.asarray([5, 2, 9, 1, 7, 3, 0, 0], jnp.int32)
    for pos0, T in ((0, 8), (8, 8), (16, 3)):
        lg, pools = M.prefill_paged(params, CFG, tokens[pos0:pos0 + T], pos0,
                                    pools, table)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), ref[pos0 + T - 1],
                                   rtol=1e-4, atol=1e-4)
    for pos in range(19, 23):
        lg, pools = M.decode_step_paged(
            params, CFG, tokens[pos][None], jnp.asarray([pos], jnp.int32),
            pools, table[None], use_pallas=use_pallas)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), ref[pos],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ps,lengths", [
    (4, [1, 5, 12, 0]),      # a page boundary, a full row, an idle slot
    (8, [17, 8, 3, 24]),
], ids=["ps4", "ps8"])
def test_latent_kernel_matches_its_oracle(ps, lengths):
    """Across page boundaries, a slot whose row ends in the scratch page,
    and an idle slot (length 0 reads as 1 scratch position, as the
    runtime's idle slots do).  Both sides accumulate in float32 from bf16
    pages: they differ by the order of the sums alone."""
    B, H, W, P, mp = 4, 4, 40, 16, 3
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (B, H, W), jnp.float32)
    pool = jax.random.normal(kp, (P, ps // 2, 2 * W)).astype(jnp.bfloat16)
    table = np.zeros((B, mp), np.int32)
    for b, n in enumerate(lengths):
        pages = -(-n // ps)
        table[b, :pages] = np.arange(1 + 3 * b, 1 + 3 * b + pages)
    lens = jnp.asarray([max(n, 1) for n in lengths], jnp.int32)
    table = jnp.asarray(table)
    got = latent_attention_pallas(q, pool, table, lens, scale=0.3,
                                  interpret=True)
    want = latent_attention_ref(q, pool, table, lens, scale=0.3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _moe_cfg(**kw):
    base = dict(d_model=16, moe=True, n_routed_experts=8, n_shared_experts=1,
                top_k=3, moe_d_ff=8, norm_topk_prob=False, dtype="float32")
    return ModelConfig(**dict(base, **kw))


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips holding two experts each: their parts, with the shared
    expert (which every chip computes alike) counted once, add up to the
    layer that holds all eight (float32 sums in another order)."""
    whole = _moe_cfg()
    p = MOE.moe_init(KEY, whole)
    x = jax.random.normal(KEY, (2, 9, 16))
    full, _ = MOE.moe_apply(p, whole, x)
    shared = L.swiglu(p["shared"], x)
    total = shared
    for s in range(4):
        cfg = _moe_cfg(n_routed_experts=2, router_experts=8,
                       expert_offset=2 * s)
        ps = dict(p, experts=jax.tree_util.tree_map(
            lambda w: w[2 * s:2 * s + 2], p["experts"]))
        part, _ = MOE.moe_apply(ps, cfg, x)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_every_token_routed_to_one_expert_is_served():
    """A router that sends all 64 tokens to expert 0 first: no capacity
    drops any of them; each output is the expert's SwiGLU of the token
    weighted by its probability, plus the other two experts' parts."""
    cfg = _moe_cfg(n_shared_experts=0)
    p = MOE.moe_init(KEY, cfg)
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = jax.random.normal(KEY, (1, 64, 16))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 4.0)
    p["router"] = p["router"].at[0, 0].set(10.0)
    out, _ = MOE.moe_apply(p, cfg, x)
    xf = x[0]
    probs = jax.nn.softmax(xf @ p["router"], -1)
    top_w, top_i = jax.lax.top_k(probs, 3)
    assert bool(jnp.all(top_i[:, 0] == 0))
    want = jnp.zeros_like(xf)
    for e in range(8):
        we = jax.tree_util.tree_map(lambda w: w[e], p["experts"])
        gate = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        want = want + gate[:, None] * L.swiglu(we, xf)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_registry_config_reduced_is_served_by_the_paged_runtime():
    """The repaired registry entry, reduced (one dense and one expert
    layer), through the continuous server: its greedy tokens are those of
    whole-sequence forwards of the growing sequence."""
    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    assert cfg.first_dense_layers == 1 and cfg.mla
    params = M.init_params(KEY, cfg)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 11)
    srv = batching.ContinuousServer(params, cfg, page_size=4, max_slots=2,
                                    num_pages=16, prefill_chunk=4)
    srv.submit(batching.Request(uid="a", tokens=prompt, max_new=4))
    got = srv.run()["a"].tokens
    seq = list(prompt)
    for _ in range(4):
        lg, _ = M.forward_logits(params, cfg,
                                 {"tokens": jnp.asarray([seq], jnp.int32)})
        seq.append(int(jnp.argmax(lg[0, -1])))
    assert list(got) == seq


@pytest.mark.parametrize("mode", ["ensemble", "speculative", "int8"])
def test_latent_pool_rejects_modes_it_does_not_serve(mode):
    params = M.init_params(KEY, CFG)
    kw = {}
    if mode == "ensemble":
        params = jax.tree_util.tree_map(lambda x: jnp.stack([x, x]), params)
        kw["mode"] = "ensemble"
    elif mode == "speculative":
        kw["speculative"] = True
    else:
        kw["kv_dtype"] = "int8"
    with pytest.raises(NotImplementedError, match="MLA"):
        batching.ContinuousServer(params, CFG, page_size=4, max_slots=2,
                                  num_pages=16, **kw)
