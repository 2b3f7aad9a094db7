"""Backend-dependent defaults shared by the kernels and serving runtimes.

Both resolve an "auto" choice from the backend JAX runs on: buffer
donation and Pallas interpret mode are TPU/GPU-only concerns that the CPU
test suite must bypass.  Nothing on the chip path relies on the fallback:
``chip_smoke.py`` refuses to run unless the backend is ``tpu``.
"""

from __future__ import annotations

import jax


def donate_argnums(argnums):
    """Buffer-donation argnums, or () on CPU where donation is an ignored
    no-op that only triggers a jax warning.  Shared by both serving
    runtimes (scan engine and continuous batching)."""
    return argnums if jax.default_backend() in ("tpu", "gpu") else ()


def resolve_interpret(interpret) -> bool:
    """Pallas ``interpret=None`` → auto-detect: compile the kernel on TPU,
    interpret everywhere else (CPU containers).  Explicit bools pass
    through untouched."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
