"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import shuffle as shf
from repro.core.consensus import sq_distance_to_consensus
from repro.core.compat import resolve_interpret
from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import layers as L

KEY = jax.random.key(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# wash_shuffle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,block_d", [(2, 100, 64), (5, 3000, 512), (8, 513, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wash_shuffle_kernel(n, d, block_d, dtype):
    x = jax.random.normal(KEY, (n, d)).astype(dtype)
    u = jax.random.uniform(jax.random.fold_in(KEY, 1), (n, d))
    perm = jnp.argsort(u, axis=0).astype(jnp.int32)
    mask = jax.random.bernoulli(jax.random.fold_in(KEY, 2), 0.4, (d,))
    out = ops.wash_shuffle(x, perm, mask, block_d=block_d)
    expect = ref.wash_shuffle_ref(x, perm, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ---------------------------------------------------------------------------
# bucketed_shuffle (TPU-native WASH plan as one fused kernel pass)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,d,p,block_d",
    [(2, 100, 0.5, 64),     # tiny
     (4, 3000, 0.2, 512),   # multi-block grid
     (5, 517, 0.5, 128),    # d not a multiple of block_d (padding path)
     (8, 129, 0.9, 128)],   # n buckets ~ d, one ragged tail lane
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bucketed_shuffle_kernel_matches_stacked(n, d, p, block_d, dtype):
    x = jax.random.normal(KEY, (n, d)).astype(dtype)
    idx = shf.bucketed_plan(jax.random.fold_in(KEY, 1), d, n, p)
    assert idx is not None
    out = ops.bucketed_shuffle(x, idx, block_d=block_d)
    expect = shf.bucketed_apply_stacked(x, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_bucketed_shuffle_kernel_distance_preserving():
    """Eq. 5: the kernel's shuffle is an exact per-coordinate permutation,
    so Σ_n ||θ_n − θ̄||² is bitwise unchanged and every coordinate's
    multiset of values is preserved across members."""
    n, d = 5, 1203
    x = jax.random.normal(KEY, (n, d))
    idx = shf.bucketed_plan(jax.random.fold_in(KEY, 2), d, n, 0.7)
    out = ops.bucketed_shuffle(x, idx, block_d=256)
    np.testing.assert_allclose(
        float(sq_distance_to_consensus(out)),
        float(sq_distance_to_consensus(x)),
        rtol=1e-6,
    )
    np.testing.assert_array_equal(
        np.sort(np.asarray(out), axis=0), np.sort(np.asarray(x), axis=0)
    )


def test_bucketed_shuffle_kernel_bucket0_identity():
    """Bucket 0's coordinates (and every unselected coordinate) must pass
    through untouched — that is the paper's (N-1)/N send-volume saving."""
    n, d = 4, 600
    x = jax.random.normal(KEY, (n, d))
    idx = shf.bucketed_plan(jax.random.fold_in(KEY, 3), d, n, 0.3)
    out = np.asarray(ops.bucketed_shuffle(x, idx, block_d=128))
    moved = set(np.asarray(idx[1:]).ravel().tolist())
    untouched = sorted(set(range(d)) - moved)
    np.testing.assert_array_equal(out[:, untouched], np.asarray(x)[:, untouched])


def test_apply_plan_stacked_pallas_matches_roll_path():
    """The stacked apply path behind --pallas-shuffle: routing bucketed
    applies through the fused kernel is pure data movement, so it must be
    bitwise-equal to the N-1-round roll path — including layered
    (scanned-blocks) leaves and leaves with no plan."""
    from repro.core.layer_index import infer_layer_ids, total_layers

    n = 4
    pop = {
        "embed": {"w": jax.random.normal(KEY, (n, 16, 8))},
        "blocks": {"w1": jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (n, 3, 8, 8))},
        "head": {"w": jax.random.normal(jax.random.fold_in(KEY, 2), (n, 8, 4))},
    }
    member = jax.tree_util.tree_map(lambda x: x[0], pop)
    lids = infer_layer_ids(member, 3)
    plan = shf.make_plan(jax.random.fold_in(KEY, 3), pop, lids,
                         total_layers(3), 0.6, mode="bucketed")
    roll = shf.apply_plan_stacked(plan, pop, mode="bucketed")
    fused = shf.apply_plan_stacked(plan, pop, mode="bucketed", use_pallas=True)
    for a, b in zip(jax.tree_util.tree_leaves(roll),
                    jax.tree_util.tree_leaves(fused)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mix_once_pallas_shuffle_config_parity():
    """MixingConfig(pallas_shuffle=True) (the vmap engine's flag) shuffles
    params AND replayed optimizer moments bitwise-identically to the
    default path."""
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.core.mixing import MixingConfig, mix_once

    n = 3
    pop = {"w": jax.random.normal(KEY, (n, 64, 8)),
           "b": jax.random.normal(jax.random.fold_in(KEY, 1), (n, 8))}
    opt = {"mu": jax.tree_util.tree_map(jnp.ones_like, pop),
           "step": jnp.zeros((n,), jnp.int32)}
    member = jax.tree_util.tree_map(lambda x: x[0], pop)
    lids = infer_layer_ids(member, 1)
    key = jax.random.fold_in(KEY, 9)
    base = MixingConfig(kind="wash_opt", base_p=0.5, mode="bucketed")
    import dataclasses
    pall = dataclasses.replace(base, pallas_shuffle=True)
    p0, o0, c0 = mix_once(key, pop, opt, base, lids, total_layers(1))
    p1, o1, c1 = mix_once(key, pop, opt, pall, lids, total_layers(1))
    for a, b in zip(jax.tree_util.tree_leaves((p0, o0)),
                    jax.tree_util.tree_leaves((p1, o1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(c0) == float(c1)


def test_resolve_interpret_auto_detect():
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    expected = jax.default_backend() != "tpu"
    assert resolve_interpret(None) is expected


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,H,KV,hd,bq,bk",
    [(1, 64, 4, 4, 16, 16, 16),   # MHA
     (2, 128, 4, 2, 32, 32, 64),  # GQA, uneven blocks
     (1, 96, 8, 1, 16, 32, 32)],  # MQA
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, S, H, KV, hd, bq, bk, dtype):
    q = jax.random.normal(KEY, (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd)).astype(dtype)
    out = ops.flash_attention(q, k, v, block_q=bq, block_k=bk)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_sliding_window(window):
    B, S, H, KV, hd = 1, 64, 4, 2, 16
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd))
    out = ops.flash_attention(q, k, v, window=window, block_q=16, block_k=16)
    expect = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, S, H, KV, hd = 1, 32, 2, 2, 16
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, hd))
    out = ops.flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged_attention (continuous-batching decode over a block-pool KV cache)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,KV,hd,P,ps,mp", [
    (3, 4, 2, 16, 8, 4, 3),    # GQA groups of 2, lengths across pages
    (2, 8, 8, 32, 16, 8, 4),   # MHA (g=1)
    (1, 2, 1, 8, 4, 2, 2),     # single slot, single kv head
    (4, 4, 2, 64, 32, 16, 2),  # wider pages
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel(B, H, KV, hd, P, ps, mp, dtype):
    ks = [jax.random.fold_in(KEY, i) for i in range(5)]
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    kp = jax.random.normal(ks[1], (P, ps, KV, hd)).astype(dtype)
    vp = jax.random.normal(ks[2], (P, ps, KV, hd)).astype(dtype)
    pt = jax.random.randint(ks[3], (B, mp), 0, P)
    lengths = jax.random.randint(ks[4], (B,), 1, mp * ps + 1)
    out = ops.paged_attention(q, kp, vp, pt, lengths)
    expect = ref.paged_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype),
    )


def test_paged_attention_length_edges():
    """length=1 (only the fresh token), page-boundary lengths, and full
    tables all mask correctly; pages past the length don't leak."""
    B, H, KV, hd, P, ps, mp = 3, 2, 2, 8, 6, 4, 3
    ks = [jax.random.fold_in(KEY, i) for i in range(3)]
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (P, ps, KV, hd))
    vp = jax.random.normal(ks[2], (P, ps, KV, hd))
    pt = jnp.array([[1, 2, 3], [3, 1, 5], [5, 4, 2]], jnp.int32)
    for lengths in ([1, 1, 1], [ps, 2 * ps, 3 * ps], [ps + 1, 1, 2 * ps - 1]):
        lv = jnp.asarray(lengths, jnp.int32)
        out = ops.paged_attention(q, kp, vp, pt, lv)
        expect = ref.paged_attention_ref(q, kp, vp, pt, lv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
    # garbage in pages past the length must not change the result
    kp2 = kp.at[4].set(1e4)
    vp2 = vp.at[4].set(-1e4)
    lv = jnp.array([ps, ps, ps], jnp.int32)  # page 4 only in masked tails
    out = ops.paged_attention(q, kp2, vp2, pt, lv)
    expect = ref.paged_attention_ref(q, kp, vp, pt, lv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quantized paged attention (int8 pools + symmetric per-page scales)
# ---------------------------------------------------------------------------


def _quantized_pool(key, P, ps, KV, hd):
    """(fp32 pool, int8 pool, per-page scale) with page-exact scales."""
    pool = jax.random.normal(key, (P, ps, KV, hd))
    scale = jnp.maximum(jnp.max(jnp.abs(pool), axis=(1, 2, 3)) / 127.0,
                        L.KV_SCALE_FLOOR)
    return pool, L.kv_quantize(pool, scale[:, None, None, None]), scale


def _fresh_int8_pool(P, ps, KV, hd):
    """A one-layer int8 pool as paged_pools_init lays one out: zero bits
    in (1, P, ps*KV, hd), floor scales, page 0 pinned to the scratch
    scale."""
    scale = jnp.full((1, P), L.KV_SCALE_FLOOR, jnp.float32)
    scale = scale.at[:, 0].set(L.KV_SCRATCH_SCALE)
    return {"q": jnp.zeros((1, P, ps * KV, hd), jnp.int8), "scale": scale}


def _dequantized_layer0(pool, KV):
    """Layer 0 of an int8 pool read back as (P, ps, KV, hd) floats, and
    its (P,) scales."""
    q = pool["q"][0]
    q = q.reshape(q.shape[0], -1, KV, q.shape[-1])
    scale = pool["scale"][0]
    return L.kv_dequantize(q, scale[:, None, None, None]), scale


@pytest.mark.parametrize("B,H,KV,hd,P,ps,mp", [
    (3, 4, 2, 16, 8, 4, 3),    # GQA groups of 2
    (2, 8, 8, 32, 16, 8, 4),   # MHA (g=1)
])
def test_paged_attention_quantized_kernel_matches_ref(B, H, KV, hd, P, ps, mp):
    """Pallas (interpret) and the jnp oracle must agree on the SAME
    quantized pools — the dequant happens inside both attends."""
    ks = [jax.random.fold_in(KEY, 40 + i) for i in range(5)]
    q = jax.random.normal(ks[0], (B, H, hd))
    _, qk, k_scale = _quantized_pool(ks[1], P, ps, KV, hd)
    _, qv, v_scale = _quantized_pool(ks[2], P, ps, KV, hd)
    pt = jax.random.randint(ks[3], (B, mp), 0, P)
    lengths = jax.random.randint(ks[4], (B,), 1, mp * ps + 1)
    out = ops.paged_attention(q, qk, qv, pt, lengths,
                              k_scale=k_scale, v_scale=v_scale)
    expect = ref.paged_attention_ref(q, qk, qv, pt, lengths,
                                     k_scale=k_scale, v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_quantized_tracks_fp32_within_tolerance():
    """int8 KV vs the fp32 pools it quantized: the pinned serving
    tolerance (per-element quant error is <= scale/2 ~ amax/254, and the
    softmax-weighted attend keeps the output inside a few steps)."""
    B, H, KV, hd, P, ps, mp = 3, 4, 2, 16, 8, 4, 3
    ks = [jax.random.fold_in(KEY, 50 + i) for i in range(5)]
    q = jax.random.normal(ks[0], (B, H, hd))
    kp, qk, k_scale = _quantized_pool(ks[1], P, ps, KV, hd)
    vp, qv, v_scale = _quantized_pool(ks[2], P, ps, KV, hd)
    pt = jax.random.randint(ks[3], (B, mp), 0, P)
    lengths = jax.random.randint(ks[4], (B,), 1, mp * ps + 1)
    exact = ref.paged_attention_ref(q, kp, vp, pt, lengths)
    quant = ops.paged_attention(q, qk, qv, pt, lengths,
                                k_scale=k_scale, v_scale=v_scale)
    np.testing.assert_allclose(np.asarray(quant), np.asarray(exact),
                               rtol=0.0, atol=5e-2)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_attention_reads_every_layer_through_the_pool_view(quantized):
    """The serving pools are (L, P, ps*KV, hd); the kernel reads layer l
    through the free view (L*P, ps, KV, hd) with the page table offset by
    l*P (and, for int8, the (L, P) scales flattened).  That must equal the
    oracle on layer l alone, for every layer."""
    B, H, KV, hd, Lyr, P, ps, mp = 3, 4, 2, 16, 3, 8, 4, 3
    ks = [jax.random.fold_in(KEY, 70 + i) for i in range(6)]
    q = jax.random.normal(ks[0], (B, H, hd))
    kp = jax.random.normal(ks[1], (Lyr, P, ps * KV, hd))
    vp = jax.random.normal(ks[2], (Lyr, P, ps * KV, hd))
    if quantized:
        k_scale = jax.random.uniform(ks[3], (Lyr, P), minval=0.01, maxval=0.1)
        v_scale = jax.random.uniform(ks[4], (Lyr, P), minval=0.01, maxval=0.1)
        kp = L.kv_quantize(kp, k_scale[..., None, None])
        vp = L.kv_quantize(vp, v_scale[..., None, None])
    pt = jax.random.randint(ks[5], (B, mp), 0, P)
    lengths = jnp.array([1, ps + 1, mp * ps], jnp.int32)
    k_view = kp.reshape(Lyr * P, ps, KV, hd)
    v_view = vp.reshape(Lyr * P, ps, KV, hd)
    for layer in range(Lyr):
        view_scales = layer_scales = {}
        if quantized:
            view_scales = dict(k_scale=k_scale.reshape(-1),
                               v_scale=v_scale.reshape(-1))
            layer_scales = dict(k_scale=k_scale[layer],
                                v_scale=v_scale[layer])
        out = paged_attention_pallas(q, k_view, v_view, pt + layer * P,
                                     lengths, interpret=True, **view_scales)
        expect = ref.paged_attention_ref(
            q, kp[layer].reshape(P, ps, KV, hd),
            vp[layer].reshape(P, ps, KV, hd), pt, lengths, **layer_scales)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5)
        # the serving layer hands the kernel exactly this view
        pool = {"q": kp, "scale": k_scale} if quantized else kp
        view, table, scale = L.paged_kernel_view(pool, layer, pt, KV)
        np.testing.assert_array_equal(np.asarray(view), np.asarray(k_view))
        np.testing.assert_array_equal(np.asarray(table),
                                      np.asarray(pt + layer * P))
        assert (scale is None) == (not quantized)


def test_paged_attention_rejects_half_specified_scales():
    B, H, KV, hd, P, ps, mp = 1, 2, 1, 8, 4, 2, 2
    q = jnp.zeros((B, H, hd))
    pool = jnp.zeros((P, ps, KV, hd))
    pt = jnp.zeros((B, mp), jnp.int32)
    lengths = jnp.ones((B,), jnp.int32)
    with pytest.raises(ValueError, match="scale"):
        ops.paged_attention(q, pool, pool, pt, lengths,
                            k_scale=jnp.ones((P,)))


def test_kv_store_rows_round_trip_error_bound():
    """Decode-step scatter into an int8 pool, read back dequantized: the
    absolute error is bounded by half a quantization step of the final
    page scale."""
    P, ps, KV, hd, B = 6, 4, 2, 8, 5
    pool = _fresh_int8_pool(P, ps, KV, hd)
    rows = jax.random.normal(jax.random.fold_in(KEY, 60), (B, KV, hd)) * 3.0
    page_idx = jnp.array([1, 2, 3, 4, 5], jnp.int32)
    offset = jnp.array([0, 1, 2, 3, 0], jnp.int32)
    pool = L.paged_store_rows(pool, 0, page_idx, offset, rows)
    got, scale = _dequantized_layer0(pool, KV)
    err = jnp.abs(got[page_idx, offset] - rows)
    bound = 0.5 * scale[page_idx][:, None, None] + 1e-6
    assert bool(jnp.all(err <= bound)), (
        f"round-trip error {float(err.max()):.4f} exceeds half a "
        f"quantization step {float(bound.max()):.4f}")


def test_kv_store_rows_duplicate_pages_keep_every_row():
    """The speculative verify step scatters several rows of one slot —
    often all into ONE page — in a single call; a gather-modify-scatter
    implementation would silently drop all but one duplicate."""
    P, ps, KV, hd = 4, 4, 2, 8
    pool = _fresh_int8_pool(P, ps, KV, hd)
    rows = jax.random.normal(jax.random.fold_in(KEY, 61), (4, KV, hd))
    page_idx = jnp.array([2, 2, 2, 2], jnp.int32)     # one page, 4 rows
    offset = jnp.arange(4, dtype=jnp.int32)
    pool = L.paged_store_rows(pool, 0, page_idx, offset, rows)
    got, scale = _dequantized_layer0(pool, KV)
    err = jnp.abs(got[2, :4] - rows)
    bound = 0.5 * scale[2] + 1e-6
    assert bool(jnp.all(err <= bound)), (
        f"duplicate-page scatter dropped rows: max err {float(err.max()):.4f}")


def test_kv_scratch_page_scale_never_adapts():
    """Page 0 is the runtime's scratch target for masked/inactive rows;
    its scale must stay pinned at KV_SCRATCH_SCALE however large the
    garbage written to it, while live pages adapt monotonically."""
    P, ps, KV, hd = 4, 4, 2, 8
    pool = _fresh_int8_pool(P, ps, KV, hd)
    huge = jnp.full((2, KV, hd), 1e4, jnp.float32)
    pool = L.paged_store_rows(pool, 0, jnp.array([0, 1], jnp.int32),
                              jnp.array([0, 0], jnp.int32), huge)
    assert float(pool["scale"][0, 0]) == L.KV_SCRATCH_SCALE
    assert float(pool["scale"][0, 1]) == pytest.approx(1e4 / 127.0)
    # growing a page's scale keeps previously-written rows within THEIR
    # original bound (rescale is monotone, error only shrinks relatively)
    small = jnp.full((1, KV, hd), 0.5, jnp.float32)
    pool = L.paged_store_rows(pool, 0, jnp.array([2], jnp.int32),
                              jnp.array([0], jnp.int32), small)
    s_before = float(pool["scale"][0, 2])
    big = jnp.full((1, KV, hd), 40.0, jnp.float32)
    pool = L.paged_store_rows(pool, 0, jnp.array([2], jnp.int32),
                              jnp.array([1], jnp.int32), big)
    assert float(pool["scale"][0, 2]) >= s_before
    got, scale = _dequantized_layer0(pool, KV)
    assert float(jnp.abs(got[2, 0] - 0.5).max()) <= \
        0.5 * float(scale[2]) + 1e-6


# ---------------------------------------------------------------------------
# rwkv6_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,H,hd,chunk", [(1, 32, 2, 8, 8), (2, 64, 2, 16, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_kernel(B, T, H, hd, chunk, dtype):
    ks = [jax.random.fold_in(KEY, i) for i in range(5)]
    r = jax.random.normal(ks[0], (B, T, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, H, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, hd)).astype(dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd))).astype(dtype)
    u = (jax.random.normal(ks[4], (H, hd)) * 0.1).astype(jnp.float32)
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk)
    expect = ref.rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
        atol=3e-1 if dtype == jnp.bfloat16 else 1e-4,
    )


def test_rwkv6_kernel_matches_model_time_mix():
    """The kernel computes the same recurrence the model's scan uses."""
    from repro.models import ssm as SSM
    from repro.configs.base import ModelConfig

    cfg = ModelConfig(num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=64, vocab_size=50, block_kind="rwkv6",
                      rwkv_head_dim=16, dtype="float32")
    B, T, H, hd = 2, 24, 2, 16
    ks = [jax.random.fold_in(KEY, i) for i in range(5)]
    r = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, H, hd))
    v = jax.random.normal(ks[2], (B, T, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hd)))
    u = jax.random.normal(ks[4], (H, hd)) * 0.1

    y_kernel = ops.rwkv6_scan(r, k, v, w, u, chunk=8)

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, hd, hd)), xs)
    y_model = jnp.moveaxis(ys, 0, 1)
    np.testing.assert_allclose(
        np.asarray(y_kernel), np.asarray(y_model), rtol=1e-4, atol=1e-4
    )
