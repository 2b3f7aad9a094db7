"""FLOP and byte counts against hand counts at small shapes, and the
peaks table."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402
from bench.architectures import dense_gqa as dense  # noqa: E402
from bench.harness import BenchError  # noqa: E402
from bench.peaks import peaks  # noqa: E402

A = dense.Arch(num_layers=2, d_model=8, num_heads=4, num_kv_heads=2,
               head_dim=2, d_ff=16, vocab_size=10, qk_norm=True,
               qkv_bias=True, rope_theta=1e4, norm_eps=1e-6,
               dtype="bfloat16")


def test_block_params_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 SwiGLU matrices
    assert dense.block_matmul_params(A) == 64 + 32 + 32 + 64 + 3 * 128


def test_causal_pairs_by_enumeration():
    for start, n in ((0, 1), (0, 5), (3, 4), (10, 1)):
        want = sum(start + i + 1 for i in range(n))
        assert flops.causal_pairs(start, n) == want


def test_train_step_by_hand():
    # per token forward: 2 x (2 blocks x 576 + head 80); attention per
    # layer and sequence: 4 x heads 4 x hd 2 x pairs (3 tokens: 6 pairs)
    fwd = 2 * (2 * 576 + 80) * 2 * 3 + 2 * 4 * 4 * 2 * 6 * 2
    assert dense.train_step(A, batch=2, seq=3) == 3 * fwd


def test_prefill_and_decode_by_hand():
    # chunk of 2 tokens at position 3: pairs 4 + 5 = 9
    want = 2 * 2 * 576 * 2 + 2 * 4 * 4 * 2 * 9 + 2 * 80
    assert dense.prefill_chunk(A, 3, 2) == want
    # two slots with contexts 3 and 7
    want = 2 * (2 * 576 + 80) * 2 + 2 * 4 * 4 * 2 * (3 + 7)
    assert dense.decode_step(A, [3, 7]) == want


def test_paged_dmas_skip_repeated_pages():
    # slot rows of 4 pages: [a b 0 0] [0 0 0 0] [c d e f] [g 0 0 0]
    # copies: a b 0 | (0 repeated) | c d e f | g 0
    assert flops.paged_attention_dmas([2, 0, 4, 1], max_pages=4) == 9
    # an idle first slot still copies the scratch page once
    assert flops.paged_attention_dmas([0, 0], max_pages=3) == 1


def test_paged_bytes_by_hand():
    # page: 16 tokens x 2 kv heads x hd 2 x 2 bytes = 128 B; K and V
    qo = 2 * 3 * 4 * 2 * 2
    assert dense.paged_attention_bytes(A, 5, 3, 16) == 2 * 5 * 128 + qo


def test_shuffle_bytes_match_the_programs_plans():
    """The leaves the byte count includes are those the program plans."""
    jax = pytest.importorskip("jax")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.layer_index import infer_layer_ids, total_layers
    from repro.core.shuffle import make_plan

    a = dense.Arch(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   head_dim=16, d_ff=128, vocab_size=256, qk_norm=True,
                   qkv_bias=True, rope_theta=1e4, norm_eps=1e-6,
                   dtype="float32")
    member = jax.eval_shape(lambda k: dense.init_weights(k, a),
                            jax.random.key(0))
    for p in (0.01, 0.1, 0.3):
        plan = make_plan(jax.random.key(1), member,
                         infer_layer_ids(member, a.num_layers),
                         total_layers(a.num_layers), p, mode="bucketed", n=2)
        sizes = sorted(int(np.prod(x.shape)) for x, q in zip(
            jax.tree_util.tree_leaves(member),
            jax.tree_util.tree_leaves(plan, is_leaf=lambda v: v is None))
            if q is not None)
        assert sorted(flops.shuffled_leaves(member, a.num_layers, 2,
                                            p)) == sizes


def test_roofline_share_names_its_bound():
    share, bound = flops.roofline_share(2.0, 100.0, 1000.0, 100.0, 1000.0)
    assert (share, bound) == (50.0, "compute")
    share, bound = flops.roofline_share(4.0, 10.0, 2000.0, 100.0, 1000.0)
    assert (share, bound) == (50.0, "memory")


def test_peaks_known_and_unknown():
    assert peaks("TPU v5 lite") == {"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9}
    with pytest.raises(BenchError):
        peaks("TPU v99")
