"""The system under test, as the benchmark reaches it: the program's
``src/`` on the import path, and its model configuration made from a
configuration file of ``bench/configs``."""

from __future__ import annotations

import sys

from bench.harness import ROOT
from bench.reference import Arch

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def program_config(a: Arch, name: str):
    """The program's ``ModelConfig`` for the sizes of ``a``."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=name, family="dense", num_layers=a.num_layers,
        d_model=a.d_model, num_heads=a.num_heads,
        num_kv_heads=a.num_kv_heads, head_dim=a.head_dim, d_ff=a.d_ff,
        vocab_size=a.vocab_size, qk_norm=a.qk_norm, qkv_bias=a.qkv_bias,
        rope_theta=a.rope_theta, norm_eps=a.norm_eps, tie_embeddings=False,
        dtype=a.dtype,
    )


def check_layout(weights_shape, cfg) -> None:
    """Raise unless the benchmark's weights have the program's layout."""
    import jax

    from repro.models import transformer as M

    want = jax.eval_shape(lambda k: M.init_params(k, cfg), jax.random.key(0))
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), weights_shape)
    exp = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise RuntimeError(f"weight layout differs from the program's: "
                           f"{got} vs {exp}")
