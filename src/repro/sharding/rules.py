"""Parameter / activation sharding rules.

A small table of name-based rules (the cases where the *direction* of the
matmul matters for collective placement: column-parallel in, row-parallel
out, expert-parallel MoE) backed by a divisibility heuristic for everything
else.  Scanned-block leading axes are never sharded (scan iterates them).

The rules produce PartitionSpecs; GSPMD propagates to activations, with
batch sharding pinned by the input specs.
"""

from __future__ import annotations

import re
from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig

PyTree = Any

# leaf-name patterns -> which *logical* dim gets the model axis
# (negative indices from the end; None = replicate)
_COL_PAR = re.compile(r"(wq|wk|wv|w1|w3|in_proj|dt_proj|w_uk|w_uv|wr|wg|frame_proj|patch_proj)$")
_ROW_PAR = re.compile(r"(wo|w2|out_proj|x_proj)$")
_REPLICATE = re.compile(
    r"(scale|bias|^b$|bq|bk|bv|b1|b2|mu|w0|u$|beta|router|conv_w|conv_b|A_log|^D$"
    r"|dt_bias|w_lora_a|w_lora_b|w_dkv|w_krope|pos|enc_pos|ln)"
)


def _leaf_name(path) -> str:
    parts = [str(p.key) if hasattr(p, "key") else str(p.idx) for p in path]
    return parts[-1] if parts else ""


def _is_blocks_leaf(path) -> bool:
    return any(
        hasattr(p, "key") and str(p.key) in ("blocks", "dense_blocks", "enc_blocks")
        for p in path
    )


def _heuristic(shape: Tuple[int, ...], model: int, skip_first: bool):
    """Shard the right-most dim divisible by the model axis (>= 2x)."""
    spec = [None] * len(shape)
    lo = 1 if skip_first else 0
    for i in range(len(shape) - 1, lo - 1, -1):
        if shape[i] % model == 0 and shape[i] // model >= 2:
            spec[i] = "model"
            break
    return P(*spec)


def param_pspec(path, leaf, cfg: ModelConfig, model_size: int) -> P:
    name = _leaf_name(path)
    shape = tuple(leaf.shape)
    nb = _is_blocks_leaf(path)
    off = 1 if nb else 0  # scanned layer axis leads blocks leaves

    if _REPLICATE.search(name):
        return P()
    if len(shape) - off < 2:
        return P()

    def with_model_at(dim_from_end: int) -> P:
        idx = len(shape) - 1 - dim_from_end
        if shape[idx] % model_size == 0 and shape[idx] // model_size >= 2:
            spec = [None] * len(shape)
            spec[idx] = "model"
            return P(*spec)
        return _heuristic(shape, model_size, nb)

    # MoE experts: expert-parallel over the model axis
    if any(hasattr(p, "key") and str(p.key) == "experts" for p in path):
        e_idx = off  # (L, E, D, F) or (E, D, F)
        if shape[e_idx] % model_size == 0:
            spec = [None] * len(shape)
            spec[e_idx] = "model"
            return P(*spec)
        return _heuristic(shape, model_size, nb)

    if name == "tok":  # (V, D): shard vocab (row-parallel embed + rsc logits)
        return with_model_at(1)
    if name == "w" and any(hasattr(p, "key") and str(p.key) == "lm_head" for p in path):
        return with_model_at(0)  # (D, V): column-parallel head
    if _COL_PAR.search(name):
        return with_model_at(0)  # output features sharded
    if _ROW_PAR.search(name):
        return with_model_at(1)  # input features sharded
    return _heuristic(shape, model_size, nb)


def param_pspecs(params: PyTree, cfg: ModelConfig, mesh) -> PyTree:
    model = mesh.shape["model"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [param_pspec(path, leaf, cfg, model) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspecs(cfg: ModelConfig, mesh, batch_size: int) -> PyTree:
    """Token/frame/patch inputs: batch over (pod-and-)data axes."""
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nd = int(np.prod([mesh.shape[a] for a in dax]))
    bspec = dax if (dax and batch_size % nd == 0) else None
    out = {"tokens": P(bspec, None)}
    if cfg.frontend == "audio":
        out["frames"] = P(bspec, None, None)
    if cfg.frontend == "vision":
        out["patches"] = P(bspec, None, None)
    return out


def cache_pspecs(cache_shapes: PyTree, cfg: ModelConfig, mesh, batch: int) -> PyTree:
    """Decode cache sharding.

    KV ring (L, B, cap, kv, hd): batch over data when divisible; otherwise
    (long_500k, B=1) shard the *context* axis over every available chip —
    context parallelism.  SSM states shard batch or the inner-dim.
    """
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nd = int(np.prod([mesh.shape[a] for a in dax])) if dax else 1
    model = mesh.shape["model"]
    batch_ok = dax and batch % nd == 0

    def spec_for(path, leaf):
        name = _leaf_name(path)
        shape = tuple(leaf.shape)
        if name in ("k", "v"):  # (L,B,cap,kv,hd)
            if batch_ok:
                cap_ax = "model" if shape[2] % model == 0 else None
                return P(None, dax, cap_ax, None, None)
            ctx = dax + ("model",)
            n = nd * model
            return P(None, None, ctx if shape[2] % n == 0 else None, None, None)
        if name in ("ckv", "krope"):  # (L,B,cap,r)
            if batch_ok:
                return P(None, dax, "model" if shape[2] % model == 0 else None, None)
            ctx = dax + ("model",)
            n = nd * model
            return P(None, None, ctx if shape[2] % n == 0 else None, None)
        if name in ("xk", "xv"):  # (L,B,frames,kv,hd)
            return P(None, dax if batch_ok else None, None, None, None)
        if name == "h":  # mamba (L,B,DI,S)
            di_ax = "model" if shape[2] % model == 0 else None
            return P(None, dax if batch_ok else None, di_ax, None)
        if name == "conv":  # (L,B,k-1,DI)
            return P(None, dax if batch_ok else None, None,
                     "model" if shape[3] % model == 0 else None)
        if name == "S":  # rwkv (L,B,H,hd,hd)
            return P(None, dax if batch_ok else None, None, None, None)
        if name in ("x_tm", "x_cm"):  # (L,B,D)
            return P(None, dax if batch_ok else None,
                     "model" if shape[2] % model == 0 else None)
        if name == "pos_ids":
            return P()
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat]
    )


def named(tree_specs: PyTree, mesh) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# stacked populations (WASH): specs for the leading ens axis + opt moments
# ---------------------------------------------------------------------------


def stage_member_specs(
    member_specs: PyTree, layer_ids: PyTree, pipe_axis: str = "pipe"
) -> PyTree:
    """Stage-shard the member specs for a pipeline mesh.

    Inserts ``pipe_axis`` on the scanned layer axis (dim 0) of every
    stacked-blocks leaf — identified by an array-valued ``layer_ids`` leaf
    (:func:`repro.core.layer_index.infer_layer_ids`), *not* by path, so
    list-of-dicts block models (whose per-block leaves have no layer axis)
    are left replicated rather than corrupted.  Everything else (embed,
    head, norms, per-block leaves of unscanned models) stays
    pipe-replicated; :mod:`repro.core.shardplan` attributes those leaves
    to an owner stage for accounting.
    """

    def _one(spec, lid):
        if isinstance(lid, int):
            return spec
        entries = tuple(spec) if spec is not None else ()
        if entries and entries[0] is not None:
            raise ValueError(
                f"scanned layer axis already sharded by {entries[0]!r}; "
                "cannot also stage-split it"
            )
        return P(pipe_axis, *entries[1:])

    return jax.tree_util.tree_map(
        _one, member_specs, layer_ids,
        is_leaf=lambda x: x is None or isinstance(x, P),
    )


def population_pspecs(
    member_specs: PyTree,
    pop_axes=("ens",),
    *,
    layer_ids: PyTree = None,
    pipe_axis: str = None,
) -> PyTree:
    """Specs for a stacked population: the leading axis is sharded over the
    population mesh axes, every member dim keeps its member-level spec.

    ``member_specs`` leaves are member-level ``PartitionSpec``s (``P()``
    replicates a member within its population shard); ``pop_axes`` is the
    tuple of mesh axes carrying the population (``("ens",)``, or
    ``("ens", "data")`` when the population divides over data too — see
    :func:`repro.core.shardplan.classify_roles`).  Passing ``pipe_axis``
    (with the matching ``layer_ids``) first routes the member specs
    through :func:`stage_member_specs`, emitting stage-sharded specs for
    pipeline meshes.
    """
    if pipe_axis is not None:
        if layer_ids is None:
            raise ValueError("pipe_axis requires layer_ids")
        member_specs = stage_member_specs(member_specs, layer_ids, pipe_axis)
    lead = pop_axes[0] if len(pop_axes) == 1 else tuple(pop_axes)

    def _one(s):
        entries = tuple(s) if s is not None else ()
        return P(lead, *entries)

    return jax.tree_util.tree_map(
        _one, member_specs, is_leaf=lambda x: x is None or isinstance(x, P)
    )


def opt_pspecs(opt_state: PyTree, pop_specs: PyTree, pop_axes=("ens",)) -> PyTree:
    """Specs for a vmapped optimizer state over a stacked population.

    Moment slots (``mu``/``nu`` — the leaves WASH+Opt shuffles) mirror the
    population's specs exactly, so moment shards line up with their
    parameter shards and the replayed shuffle plan indexes both with the
    same local coordinates.  Everything else (step counters) is sharded
    over the population axes only.
    """
    lead = pop_axes[0] if len(pop_axes) == 1 else tuple(pop_axes)
    return {
        k: pop_specs if k in ("mu", "nu")
        else jax.tree_util.tree_map(lambda _: P(lead), opt_state[k])
        for k in opt_state
    }
