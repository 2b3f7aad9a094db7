"""The system under test, as the benchmark reaches it: the program's
``src/`` on the import path, and the check that the benchmark's weights
have the program's layout.  The program's model configuration for a
cell is its architecture's (``bench/architectures/<name>.py``)."""

from __future__ import annotations

import sys

from bench.harness import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


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
