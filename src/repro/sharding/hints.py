"""In-model sharding hints (GSPMD constraints).

Model code is mesh-agnostic; the launcher activates hints for the current
mesh via :func:`use_hints`.  When inactive (unit tests, single device),
``constrain`` is the identity, so the model stays runnable anywhere.

This is the §Perf lever for the MoE dispatch: without an explicit
constraint GSPMD keeps the (groups, experts, capacity, d_model) buffer
replicated over the model axis and only 1/16th of the chips do expert
math; pinning it to P(data, model, None, None) makes the expert einsum
fully expert-parallel.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

_state = threading.local()


def _axes():
    return getattr(_state, "axes", None)


@contextlib.contextmanager
def use_hints(data_axes: Sequence[str], model_axis: Optional[str] = "model"):
    """Activate sharding hints for code traced inside this context."""
    prev = _axes()
    _state.axes = (tuple(data_axes), model_axis)
    try:
        yield
    finally:
        _state.axes = prev


def active() -> bool:
    """Whether hints are active: code is being traced for a mesh."""
    return _axes() is not None


def constrain(x, kind: str):
    """Attach a sharding constraint if hints are active.

    kinds:
      moe_buffer   (groups, E, C, D)   -> P(data, model, None, None)
      moe_buffer_global (E, C, D)      -> P(model, None, None)
      activations  (B, S, D)           -> P(data, None, None)
    """
    axes = _axes()
    if axes is None:
        return x
    data_axes, model_axis = axes
    da = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    if kind == "moe_buffer":
        spec = P(da, model_axis, None, None)
    elif kind == "moe_buffer_local":
        # groups data-sharded, experts replicated: the dispatch scatter
        # stays device-local (each model-axis replica redundantly builds
        # its copy); the subsequent moe_buffer reshard is a local slice.
        spec = P(da, None, None, None)
    elif kind == "moe_buffer_global":
        spec = P(model_axis, None, None)
    elif kind == "moe_group_dm":
        # one token-group per chip: dispatch scatters stay fully local
        # and the expert exchange is a true all-to-all (G over BOTH axes)
        spec = P(tuple(data_axes) + (model_axis,), None, None, None)
    elif kind == "tokens_dm":
        spec = P(tuple(data_axes) + (model_axis,), None, None)
    elif kind == "activations":
        spec = P(da, None, None)
    else:
        raise ValueError(f"unknown sharding hint kind {kind!r}")
    return jax.lax.with_sharding_constraint(x, spec)
