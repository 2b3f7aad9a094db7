"""Production meshes.

All constructors are FUNCTIONS — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).

  single pod : (16, 16)        ("data", "model")            — 256 v5e chips
  multi-pod  : (2, 16, 16)     ("pod", "data", "model")     — 512 chips
  ensemble   : (N, 256//N, 16) ("ens", "data", "model")     — WASH population
               single-pod; multi-pod WASH maps ens onto the pod axis.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def _largest_divisor(x: int, cap: int) -> int:
    """Largest divisor of ``x`` that is <= ``cap`` (>= 1)."""
    return max(s for s in range(1, max(min(x, cap), 1) + 1) if x % s == 0)


def make_host_ensemble_mesh(population: int):
    """Ens-only mesh over this host's actual devices (fused-engine default).

    One member per device when the population divides the device count;
    otherwise the largest divisor of the population that fits (1-device CPU
    fallback: the whole population is one shard_map block and every
    ppermute degenerates to a local roll)."""
    return _mk((_largest_divisor(population, len(jax.devices())),), ("ens",))


HOST_MESH_AXES = {
    "ens": ("ens",),
    "ens_dp": ("ens", "data"),
    "ens_dp_mp": ("ens", "data", "model"),
    "ens_pp": ("ens", "pipe"),
    "ens_dp_pp": ("ens", "data", "pipe"),
}


def make_host_mesh(
    population: int,
    kind: str = "ens",
    *,
    mesh_shape=None,
    pp_stages: int = None,
):
    """Host-device-count-clamped multi-axis mesh for the fused engine.

      ens        (E,)        — the existing ens-only default
      ens_dp     (E, D)      — population + data axes
      ens_dp_mp  (E, D, M)   — population + data + model axes
      ens_pp     (E, S)      — population + pipeline-stage axes
      ens_dp_pp  (E, D, S)   — population + data + pipeline-stage axes

    Automatic fill: E is the largest divisor of the population that fits
    the host (as in :func:`make_host_ensemble_mesh`); for ``ens_pp``/
    ``ens_dp_pp`` the pipe axis takes ``pp_stages`` (which must divide the
    remaining devices; default 1); the model axis takes the largest
    divisor of what is left (replacing the old hard-coded 2-or-1 fill);
    the data axis absorbs the remainder.  Axes are never padded past the
    host's device count, so a 1-device host degenerates every kind to the
    all-ones mesh.

    ``mesh_shape`` overrides the fill entirely: a tuple matching the
    kind's axes exactly (e.g. ``(2, 2, 2)`` for ``ens_dp_mp``), validated
    against the host's device count with a clear error when it does not
    divide.
    """
    if kind not in HOST_MESH_AXES:
        raise ValueError(f"unknown host mesh kind {kind!r}")
    axes = HOST_MESH_AXES[kind]
    ndev = len(jax.devices())
    if mesh_shape is not None:
        shape = tuple(int(s) for s in mesh_shape)
        if len(shape) != len(axes) or any(s < 1 for s in shape):
            raise ValueError(
                f"mesh shape {shape} does not match mesh kind {kind!r} "
                f"(axes {axes}: need {len(axes)} sizes >= 1)"
            )
        total = 1
        for s in shape:
            total *= s
        if ndev % total:
            raise ValueError(
                f"mesh shape {shape} needs {total} devices, which does not "
                f"divide this host's {ndev}"
            )
        if population % shape[0]:
            raise ValueError(
                f"population {population} must divide over ens axis of "
                f"size {shape[0]}"
            )
        return _mk(shape, axes)
    if kind == "ens":
        return make_host_ensemble_mesh(population)
    e = _largest_divisor(population, ndev)
    rest = ndev // e
    sizes = {"ens": e}
    if "pipe" in axes:
        s = 1 if pp_stages is None else int(pp_stages)
        if s < 1 or rest % s:
            raise ValueError(
                f"pp_stages={s} must divide the {rest} devices left after "
                f"ens={e} (host has {ndev} devices); pass mesh_shape for "
                f"an explicit layout"
            )
        sizes["pipe"] = s
        rest //= s
    if "model" in axes:
        sizes["model"] = _largest_divisor(rest, rest)
        rest //= sizes["model"]
    if "data" in axes:
        sizes["data"] = rest
    return _mk(tuple(sizes[a] for a in axes), axes)


def make_host_data_mesh():
    """Data-only mesh over every device on this host (serving default).

    The serving engine shards the request batch over ``data`` and
    replicates params — the natural layout for soup/member/ensemble modes,
    where each model instance fits a chip and throughput comes from batch
    parallelism.  A 1-device host degenerates to the (1,) mesh."""
    return _mk((len(jax.devices()),), ("data",))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_ensemble_mesh(population: int = 4, *, multi_pod: bool = False):
    """Mesh with an explicit ens axis for WASH population training.

    Multi-pod: the population IS the pod axis (the paper's distributed
    story — shuffle crosses the pod boundary, everything else stays inside
    a pod).  Single-pod: the data axis is split (ens, data).
    """
    if multi_pod:
        assert population == 2, "multi-pod ensemble maps members onto 2 pods"
        return _mk((2, 16, 16), ("ens", "data", "model"))
    assert 256 % (population * 16) == 0, "population must divide the data axis"
    return _mk((population, 256 // (population * 16), 16), ("ens", "data", "model"))


def data_axes(mesh) -> tuple:
    """Axes over which the global batch is sharded."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
