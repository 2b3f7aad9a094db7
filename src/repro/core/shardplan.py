"""Shard-local WASH mixing plans for ens×data×model meshes.

The stacked/bucketed mixing paths (:mod:`repro.core.mixing`) build one
*global* plan per parameter leaf and assume the leaf is replicated within
a member.  On a production ``(ens, data, model)`` mesh that is exactly
wrong: gathering globally-indexed coordinates breaks the parameter
sharding, and XLA replicates the selected payload over each member's
chips before the ens-axis permute.  This module is the planner that makes
WASH mesh-native:

  * **Axis roles** (:func:`classify_roles`): every mesh axis gets an
    explicit :class:`AxisRole` — ``ENS`` axes carry the population (the
    ``ens`` axis, plus data axes when the population divides over them —
    then every chip holds whole members and per-member compute stays
    bitwise-identical to the ens-only engine); leftover ``DATA`` axes
    split each member's batch (gradients ``pmean`` over them); ``MODEL``
    axes shard members and are visible to the planner only through the
    PartitionSpecs; a ``PIPE`` axis partitions each member's blocks into
    contiguous pipeline stages (:func:`repro.core.layer_index.
    stage_layer_bounds`).  A size-1 ``pipe`` axis is dropped entirely, so
    degenerate pipeline meshes take the single-stage (bitwise-identical)
    paths.
  * **Per-stage plans**: a pipe-sharded blocks leaf draws one sub-plan per
    stage from that stage's own budget, in stage-*local* coordinates.
    Every chip builds all stages' sub-plans from the same key
    (``fold_in(leaf_key, stage)``), concatenates them, and masks foreign
    stages' columns to the out-of-range sentinel ``d_local`` — JAX clamps
    OOB gathers and *drops* OOB scatters, so the masked columns move no
    data, the plan array stays SPMD-uniform (one trace), and the
    ``ppermute`` rings run purely within each stage's ens slice.
    :func:`static_stage_mix_comm` accounts each stage exactly;
    :func:`static_shard_mix_comm` is their literal sum.
  * **Local shard shapes** are derived once, host-side, from a member
    template + per-leaf ``PartitionSpec`` via ``jax.eval_shape``-style
    shape math and spec slicing (:func:`plan_population_mixing`); no
    device math is scattered at call sites.
  * **Per-shard budgets**: each shard draws its slice of the *global*
    bucketed budget — ``k_per_local = k_per_global // num_shards``
    (per-layer for scanned-blocks leaves) — so the summed per-shard
    communication volume never exceeds the global plan's (asserted in
    ``tests/test_shardplan.py``).  An unsharded leaf keeps the exact
    global budget, which makes the single-``ens``-axis path bitwise
    identical to :func:`repro.core.mixing.mix_collective_blocked`.
  * **Plan keys** fold the chip's shard position *per leaf*: the leaf key
    (``fold_in(step_key, leaf_index)``, matching
    :func:`repro.core.shuffle.make_plan`) is folded with the linearized
    coordinate over the mesh axes that actually shard that leaf.  Shards
    therefore draw independent permutations, while chips that hold
    replicas of the same shard (e.g. data-replicated leaves) fold the
    same position and stay consistent — and the ``ens``-axis ``ppermute``
    neighbours agree on every bucket.  Eq. (4)/(5) hold per shard, hence
    globally, and the permute payload is the paper's p·d/chips.

Public entry points: :func:`plan_population_mixing` (the static planner),
:func:`mix_collective_sharded` (the in-``shard_map`` mixing step the fused
engine calls), :func:`static_shard_mix_comm` (exact host-side float64
accounting), and :func:`make_shardlocal_mixer` (a standalone
``shard_map``-wrapped mixer; ``repro.launch.dryrun`` delegates here).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import shuffle as shf
from repro.core.layer_index import (
    infer_layer_ids,
    stage_layer_bounds,
    stage_of_depth,
    total_layers,
)
from repro.core.mixing import MixingConfig, momentum_like_leaves
from repro.core.schedules import layer_probability, layer_probability_array

PyTree = Any

PIPE_AXIS = "pipe"


# ---------------------------------------------------------------------------
# axis classification
# ---------------------------------------------------------------------------


def data_like_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry batch/data parallelism (mirrors launch.mesh.data_axes
    without importing launch from core)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class AxisRole(enum.Enum):
    """What a mesh axis *means* to the population planner."""

    ENS = "ens"      # carries the population (ppermute rings run here)
    DATA = "data"    # splits each member's batch (gradients pmean here)
    MODEL = "model"  # shards member parameters (visible via PartitionSpecs)
    PIPE = "pipe"    # partitions each member's blocks into pipeline stages


@dataclasses.dataclass(frozen=True)
class AxisRoles:
    """Explicit per-axis role assignment for one mesh.

    The single source of truth the planner, the fused engines, and the
    accounting all read; replaces the old ``(pop_axes, dp_axes)`` tuple
    plumbing (anything not in either tuple used to be implicitly
    model-ish).  Size-1 ``pipe`` axes never appear here — they are dropped
    at classification time so degenerate pipeline meshes take the
    single-stage code paths bitwise.
    """

    roles: Tuple[Tuple[str, AxisRole], ...]

    def axes(self, role: AxisRole) -> Tuple[str, ...]:
        return tuple(a for a, r in self.roles if r == role)

    @property
    def pop_axes(self) -> Tuple[str, ...]:
        return self.axes(AxisRole.ENS)

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return self.axes(AxisRole.DATA)

    @property
    def model_axes(self) -> Tuple[str, ...]:
        return self.axes(AxisRole.MODEL)

    @property
    def pipe_axis(self) -> Optional[str]:
        p = self.axes(AxisRole.PIPE)
        return p[0] if p else None

    def role_of(self, axis: str) -> Optional[AxisRole]:
        return dict(self.roles).get(axis)


def classify_roles(
    mesh,
    n: int,
    *,
    pop_axes: Optional[Tuple[str, ...]] = None,
    dp_axes: Optional[Tuple[str, ...]] = None,
) -> AxisRoles:
    """Assign an :class:`AxisRole` to every mesh axis for a population of n.

    Population axes always start with ``ens``.  Data axes are *absorbed*
    into the population when the population divides over ens×data — each
    chip then holds whole members and the per-member update needs no
    gradient collective, which keeps multi-axis runs bitwise-identical to
    the ens-only engine.  Otherwise data axes split each member's batch
    (``DATA``) and gradients are ``pmean``-ed over them.  An axis named
    ``pipe`` (of size > 1) becomes the pipeline-stage axis; every other
    axis is ``MODEL``.  Callers may pin ``pop_axes``/``dp_axes`` explicitly
    (the standalone mixer derives them from its population specs); the
    pipe axis is still recognized by name.
    """
    names = mesh.axis_names
    if pop_axes is None or dp_axes is None:
        if "ens" not in names:
            raise ValueError(f"population mesh needs an 'ens' axis; got {names}")
        e = int(mesh.shape["ens"])
        if n % e:
            raise ValueError(
                f"population {n} must divide over ens axis of size {e}"
            )
        # size-1 data axes carry nothing: keep them out of both groups so
        # degenerate meshes take the trivial (bitwise-identical) body
        data = tuple(
            a for a in data_like_axes(mesh) if int(mesh.shape[a]) > 1
        )
        dsz = int(np.prod([mesh.shape[a] for a in data])) if data else 1
        if data and (n // e) % dsz == 0:
            auto_pop, auto_dp = ("ens",) + data, ()
        else:
            auto_pop, auto_dp = ("ens",), data
        pop_axes = auto_pop if pop_axes is None else tuple(pop_axes)
        dp_axes = auto_dp if dp_axes is None else tuple(dp_axes)
    else:
        pop_axes, dp_axes = tuple(pop_axes), tuple(dp_axes)

    roles = []
    for a in names:
        if a in pop_axes:
            roles.append((a, AxisRole.ENS))
        elif a in dp_axes:
            roles.append((a, AxisRole.DATA))
        elif a == PIPE_AXIS and int(mesh.shape[a]) > 1:
            roles.append((a, AxisRole.PIPE))
        else:
            roles.append((a, AxisRole.MODEL))
    return AxisRoles(roles=tuple(roles))


def classify_axes(mesh, n: int) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    """Back-compat view of :func:`classify_roles`: ``(pop_axes, dp_axes)``."""
    r = classify_roles(mesh, n)
    return r.pop_axes, r.dp_axes


# ---------------------------------------------------------------------------
# the static planner
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafShardInfo:
    """Static per-leaf shard geometry + bucketed budget (host-side only)."""

    index: int                      # plan-key fold index (flatten order)
    member_shape: Tuple[int, ...]   # global member shape
    local_shape: Tuple[int, ...]    # this chip's member-shard shape
    sharded_dims: Tuple[Tuple[int, str, int], ...]  # (dim, axis, local_size)
    num_shards: int                 # model shards only (pipe excluded)
    layered: bool
    counts_local: Optional[Tuple[int, ...]]  # layered per-layer budget (all L)
    k_per_local: int                # non-layered per-bucket count (0: no plan)
    sel_local: int                  # scalars selected per shard per step
    d_local: int                    # flat size of the local member shard
    d_rest_local: int               # layered: per-layer local flat size
    # pipeline fields (single-stage plans: stage=0, bounds/k_per None)
    stage: int = 0                  # owner stage of a non-stage-split leaf
    stage_bounds: Optional[Tuple[Tuple[int, int], ...]] = None
    stage_k_per: Optional[Tuple[int, ...]] = None  # per-stage bucket budget

    @property
    def shard_axes(self) -> Tuple[str, ...]:
        return tuple(a for _, a, _ in self.sharded_dims)

    @property
    def stage_split(self) -> bool:
        return self.stage_k_per is not None


@dataclasses.dataclass(frozen=True)
class PopulationPlan:
    """Everything the fused engine needs to mix a sharded population.

    Built once, host-side, by :func:`plan_population_mixing`; consumed at
    trace time inside ``shard_map`` (never itself traced).
    """

    roles: AxisRoles
    axis_sizes: Tuple[Tuple[str, int], ...]
    num_stages: int                 # pipe-axis size (1: no pipeline)
    n: int                          # global population
    n_local: int                    # members per pop-shard
    infos: Tuple[Optional[LeafShardInfo], ...]  # flatten order
    treedef: Any
    mcfg: MixingConfig

    @property
    def pop_axes(self) -> Tuple[str, ...]:
        return self.roles.pop_axes

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return self.roles.dp_axes

    @property
    def pipe_axis(self) -> Optional[str]:
        return self.roles.pipe_axis

    @property
    def any_sharded(self) -> bool:
        return any(i is not None and i.sharded_dims for i in self.infos)

    def size(self, axis: str) -> int:
        return dict(self.axis_sizes)[axis]


def _local_leaf_geometry(shape, spec, mesh, roles: AxisRoles, layered=False):
    """Spec slicing: the chip-local shard shape of one *member* leaf.

    Returns ``(local_shape, sharded_dims, num_shards, pipe_stages)``.
    The pipe axis is handled specially: it may only appear alone on the
    scanned layer axis (dim 0) of a stacked-blocks leaf, never enters
    ``sharded_dims``/``num_shards`` (plan keys must NOT fold the stage —
    every chip builds all stages' sub-plans), and tolerates uneven layer
    counts (``local[0]`` is the floor; the planner's per-stage accounting
    uses :func:`repro.core.layer_index.stage_layer_bounds`, the engines
    require exact divisibility).
    """
    entries = tuple(spec) if spec is not None else ()
    local = list(shape)
    sharded_dims = []
    num_shards = 1
    pipe_stages = 1
    pipe = roles.pipe_axis
    for dim, e in enumerate(entries):
        if e is None:
            continue
        axes = (e,) if isinstance(e, str) else tuple(e)
        for a in axes:
            if roles.role_of(a) in (AxisRole.ENS, AxisRole.DATA):
                raise ValueError(
                    f"param spec uses axis {a!r}, which carries the "
                    f"population/batch — member specs may only use model/"
                    f"pipe-type axes (mesh axes {mesh.axis_names}, "
                    f"roles {roles.roles})"
                )
        if pipe is not None and pipe in axes:
            if axes != (pipe,):
                raise ValueError(
                    f"the pipe axis cannot share a dim with {axes}"
                )
            if not (layered and dim == 0):
                raise ValueError(
                    f"the pipe axis may only shard the scanned layer axis "
                    f"(dim 0) of stacked-blocks leaves; got dim {dim} of "
                    f"shape {shape} (layered={layered})"
                )
            pipe_stages = int(mesh.shape[pipe])
            local[dim] = shape[0] // pipe_stages
            continue
        sz = int(np.prod([mesh.shape[a] for a in axes]))
        if sz == 1:
            continue
        if local[dim] % sz:
            raise ValueError(
                f"leaf dim {dim} of shape {shape} not divisible by mesh "
                f"axes {axes} (size {sz})"
            )
        local[dim] //= sz
        if len(axes) != 1:
            raise ValueError(
                f"multi-axis sharding of one dim ({axes}) is not supported "
                "by the shard-local planner yet"
            )
        sharded_dims.append((dim, axes[0], local[dim]))
        num_shards *= sz
    return tuple(local), tuple(sharded_dims), num_shards, pipe_stages


def plan_population_mixing(
    mesh,
    member_tpl: PyTree,
    member_specs: PyTree,
    mcfg: MixingConfig,
    layer_ids: PyTree,
    tl: int,
    n: int,
    *,
    pop_axes: Optional[Tuple[str, ...]] = None,
    dp_axes: Optional[Tuple[str, ...]] = None,
) -> PopulationPlan:
    """Build the static shard-local mixing plan for a population.

    ``member_tpl`` is a single-member pytree (arrays or
    ``ShapeDtypeStruct``); ``member_specs`` its per-leaf ``PartitionSpec``s
    (``None``/``P()`` = replicated).  ``layer_ids``/``tl`` follow
    :func:`repro.core.shuffle.make_plan`; per-leaf key folding matches it
    exactly, so an entirely-unsharded plan reproduces the global plan
    bitwise.  A ``pipe`` mesh axis (size > 1) splits stage-sharded blocks
    leaves into per-stage budgets and assigns every other leaf an owner
    stage by depth.
    """
    roles = classify_roles(mesh, n, pop_axes=pop_axes, dp_axes=dp_axes)
    pop_axes, dp_axes = roles.pop_axes, roles.dp_axes
    pipe = roles.pipe_axis
    num_stages = int(mesh.shape[pipe]) if pipe is not None else 1
    member_sds = jax.eval_shape(lambda: member_tpl)
    leaves, treedef = jax.tree_util.tree_flatten(member_sds)
    spec_leaves = jax.tree_util.tree_flatten(
        member_specs, is_leaf=lambda x: x is None or isinstance(x, P)
    )[0]
    lid_leaves = jax.tree_util.tree_flatten(layer_ids)[0]
    if not (len(leaves) == len(spec_leaves) == len(lid_leaves)):
        raise ValueError(
            f"member/specs/layer_ids trees disagree: {len(leaves)} vs "
            f"{len(spec_leaves)} vs {len(lid_leaves)} leaves"
        )

    infos = []
    for i, (leaf, spec, lid) in enumerate(zip(leaves, spec_leaves, lid_leaves)):
        shape = tuple(int(s) for s in leaf.shape)
        layered = not isinstance(lid, int)
        local, sharded_dims, num_shards, pipe_stages = _local_leaf_geometry(
            shape, spec, mesh, roles, layered=layered
        )
        d_local = int(np.prod(local, dtype=np.int64)) if local else 1
        if layered:
            if not shape:
                raise ValueError(f"layered leaf {i} must have a layer axis")
            if sharded_dims and any(d == 0 for d, _, _ in sharded_dims):
                raise ValueError(
                    f"leaf {i}: the scanned layer axis cannot be sharded"
                )
            L = shape[0]
            p_vec = np.clip(
                layer_probability_array(mcfg.base_p, lid, tl, mcfg.schedule),
                0.0, 1.0,
            )
            d_rest = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
            d_rest_local = (
                int(np.prod(local[1:], dtype=np.int64)) if len(local) > 1 else 1
            )
            counts_global = [int(round(float(p_vec[l]) * d_rest)) for l in range(L)]
            counts_local = tuple(c // num_shards for c in counts_global)
            if pipe_stages > 1:
                # per-stage budgets: each stage pools only its own layers'
                # counts and takes an independent floor — the paper's Eq. 6
                # schedule applied stage-locally, so the shuffle ring never
                # crosses a stage boundary
                bounds = stage_layer_bounds(L, pipe_stages)
                stage_k_per = tuple(
                    sum(
                        min(c, d_rest_local)
                        for c in counts_local[lo:hi] if c > 0
                    ) // n
                    for lo, hi in bounds
                )
                k_per = sum(stage_k_per)
                infos.append(LeafShardInfo(
                    index=i, member_shape=shape, local_shape=local,
                    sharded_dims=sharded_dims, num_shards=num_shards,
                    layered=True, counts_local=counts_local,
                    k_per_local=k_per, sel_local=k_per * n,
                    d_local=d_local, d_rest_local=d_rest_local,
                    stage_bounds=bounds, stage_k_per=stage_k_per,
                ))
                continue
            pooled = sum(
                min(c, d_rest_local) for c in counts_local if c > 0
            )
            k_per = pooled // n
            sel = k_per * n
            infos.append(LeafShardInfo(
                index=i, member_shape=shape, local_shape=local,
                sharded_dims=sharded_dims, num_shards=num_shards,
                layered=True, counts_local=counts_local, k_per_local=k_per,
                sel_local=sel, d_local=d_local, d_rest_local=d_rest_local,
            ))
            continue
        p_l = layer_probability(mcfg.base_p, int(lid), tl, mcfg.schedule)
        d_global = int(np.prod(shape, dtype=np.int64)) if shape else 1
        k_per_global = (
            shf.bucket_count(d_global, n, min(p_l, 1.0)) if p_l > 0.0 else 0
        )
        k_per_local = k_per_global // num_shards
        infos.append(LeafShardInfo(
            index=i, member_shape=shape, local_shape=local,
            sharded_dims=sharded_dims, num_shards=num_shards,
            layered=False, counts_local=None, k_per_local=k_per_local,
            sel_local=k_per_local * n, d_local=d_local, d_rest_local=0,
            stage=(
                stage_of_depth(int(lid), tl - 2, num_stages)
                if num_stages > 1 else 0
            ),
        ))

    sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    m = int(np.prod([sizes[a] for a in pop_axes]))
    if n % m:
        raise ValueError(
            f"population {n} must divide over pop axes {pop_axes} (size {m})"
        )
    return PopulationPlan(
        roles=roles,
        axis_sizes=tuple(sizes.items()),
        num_stages=num_stages,
        n=n, n_local=n // m,
        infos=tuple(infos), treedef=treedef, mcfg=mcfg,
    )


# ---------------------------------------------------------------------------
# traced pieces (run inside shard_map)
# ---------------------------------------------------------------------------


def _shard_position(info: LeafShardInfo, pplan: PopulationPlan):
    """Linearized coordinate of this chip over the axes sharding ``info``.

    Chips holding replicas of the same shard (axes absent from the leaf's
    spec) compute the same position, so replicated copies draw identical
    plans and stay consistent."""
    pos = jnp.zeros((), jnp.int32)
    for _, a, _ in info.sharded_dims:
        pos = pos * pplan.size(a) + lax.axis_index(a)
    return pos


def _stage_split_plan(k: jax.Array, info: LeafShardInfo, pplan: PopulationPlan):
    """One SPMD-uniform plan for a pipe-sharded blocks leaf.

    Every chip builds *all* stages' sub-plans (stage ``s`` from
    ``fold_in(k, s)``, indices in stage-local coordinates over that
    stage's layer slice of the counts) and concatenates them along the
    bucket dim, so the traced shapes agree across the mesh.  Columns
    owned by other stages are then masked to the sentinel ``d_local``
    (one past the local flat shard): JAX *clamps* out-of-range gathers
    (the read value is discarded by the matching dropped scatter) and
    *drops* out-of-range scatters, so masked columns move no data and
    :mod:`repro.core.shuffle` needs no pipe-awareness at all.
    """
    if info.member_shape[0] % len(info.stage_k_per):
        raise ValueError(
            f"stage-split plans need num_layers divisible by the stage "
            f"count; got {info.member_shape[0]} layers over "
            f"{len(info.stage_k_per)} stages (the planner's accounting "
            f"allows uneven stages, executing them does not)"
        )
    subs, stage_ids = [], []
    for s, (lo, hi) in enumerate(info.stage_bounds):
        if info.stage_k_per[s] == 0:
            continue
        sub = shf.bucketed_plan_layered(
            jax.random.fold_in(k, s), hi - lo, info.d_rest_local,
            pplan.n, None, counts=info.counts_local[lo:hi],
        )
        subs.append(sub)
        stage_ids.append(np.full((info.stage_k_per[s],), s, np.int32))
    if not subs:
        return None
    idx = jnp.concatenate(subs, axis=1)
    sid = jnp.asarray(np.concatenate(stage_ids))
    mine = sid[None, :] == lax.axis_index(pplan.pipe_axis)
    return jnp.where(mine, idx, jnp.int32(info.d_local))


def build_local_plans(key: jax.Array, pplan: PopulationPlan) -> PyTree:
    """Build this chip's bucketed plans (one per leaf, indices into the
    *local flat member shard*).  Must run inside ``shard_map`` when any
    leaf is sharded (the key fold reads ``axis_index``).  Stage-split
    leaves get the sentinel-masked concatenation of per-stage sub-plans
    (:func:`_stage_split_plan`); the stage is *not* folded into the plan
    key — all chips must agree on every stage's sub-plan so the masked
    columns line up."""
    plans = []
    for info in pplan.infos:
        if info is None or info.sel_local == 0:
            plans.append(None)
            continue
        k = jax.random.fold_in(key, info.index)
        if info.sharded_dims:
            k = jax.random.fold_in(k, _shard_position(info, pplan))
        if info.stage_split:
            plans.append(_stage_split_plan(k, info, pplan))
        elif info.layered:
            plans.append(shf.bucketed_plan_layered(
                k, len(info.counts_local), info.d_rest_local, pplan.n,
                None, counts=info.counts_local,
            ))
        else:
            plans.append(shf.bucketed_plan(
                k, info.d_local, pplan.n, 0.0, k_per=info.k_per_local
            ))
    return jax.tree_util.tree_unflatten(pplan.treedef, plans)


def all_gather_population(params: PyTree, pplan: PopulationPlan) -> PyTree:
    """Reconstruct full member leaves from model shards (tiled all-gather
    per sharded dim; bitwise — gathering moves values, it never computes).
    Leaves carry a leading local-population axis, so dim k of the member
    is axis k+1 of the leaf."""
    flat = jax.tree_util.tree_flatten(params)[0]
    out = []
    for info, leaf in zip(pplan.infos, flat):
        for dim, a, _ in info.sharded_dims:
            leaf = lax.all_gather(leaf, a, axis=dim + 1, tiled=True)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(pplan.treedef, out)


def shard_population(tree: PyTree, pplan: PopulationPlan) -> PyTree:
    """This chip's model-shard of full member leaves (inverse of
    :func:`all_gather_population`; an exact slice)."""
    flat = jax.tree_util.tree_flatten(tree)[0]
    out = []
    for info, leaf in zip(pplan.infos, flat):
        for dim, a, lsz in info.sharded_dims:
            leaf = lax.dynamic_slice_in_dim(
                leaf, lax.axis_index(a) * lsz, lsz, axis=dim + 1
            )
        out.append(leaf)
    return jax.tree_util.tree_unflatten(pplan.treedef, out)


def mix_collective_sharded(
    key: jax.Array,
    params: PyTree,
    opt_state: Optional[PyTree],
    cfg: MixingConfig,
    pplan: PopulationPlan,
    gate: Optional[jax.Array],
    use_pallas: bool = False,
) -> Tuple[PyTree, Optional[PyTree]]:
    """Shard-local mixing on a block of members under ``shard_map``.

    The multi-axis generalization of
    :func:`repro.core.mixing.mix_collective_blocked`: ``params`` leaves
    carry a leading local-population axis and hold each member's
    *model-shard*; WASH plans come from :func:`build_local_plans` and the
    bucket exchanges ``ppermute`` over ``pplan.pop_axes``; PAPA pulls
    ``pmean`` over the same axes (elementwise, so shard-local application
    is exact).  ``gate`` masks the result as in the blocked path (pass
    ``None`` for an ungated mixer).  Communication is accounted host-side
    via :func:`static_shard_mix_comm`, never here.
    """
    if cfg.kind == "none":
        return params, opt_state

    ax = pplan.pop_axes
    # the Pallas bucketed-shuffle kernel indexes without OOB masking, so
    # stage-split plans (sentinel columns) must take the lax path
    use_pallas = use_pallas and pplan.num_stages == 1

    def _gated(new_tree, old_tree):
        if gate is None:
            return new_tree
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(gate > 0, a, b), new_tree, old_tree
        )

    if cfg.kind in ("wash", "wash_opt"):
        with jax.named_scope("wash"):
            with jax.named_scope("plan"):
                plan = build_local_plans(key, pplan)
            with jax.named_scope("shuffle"):
                new_params = shf.apply_plan_collective_blocked(
                    plan, params, ax, use_pallas=use_pallas
                )
                new_opt = opt_state
                if cfg.shuffles_optimizer() and opt_state is not None:
                    new_opt = dict(opt_state)
                    for mk, mv in momentum_like_leaves(opt_state,
                                                       params).items():
                        new_opt[mk] = _gated(
                            shf.apply_plan_collective_blocked(
                                plan, mv, ax, use_pallas=use_pallas
                            ),
                            mv,
                        )
            return _gated(new_params, params), new_opt

    if cfg.kind == "papa":
        pulled = jax.tree_util.tree_map(
            lambda x: cfg.papa_alpha * x
            + (1.0 - cfg.papa_alpha)
            * lax.pmean(jnp.mean(x, axis=0, keepdims=True), ax),
            params,
        )
        return _gated(pulled, params), opt_state

    if cfg.kind == "papa_all":
        avg = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(
                lax.pmean(jnp.mean(x, axis=0, keepdims=True), ax), x.shape
            ),
            params,
        )
        return _gated(avg, params), opt_state

    raise ValueError(f"unknown mixing kind {cfg.kind!r}")


# ---------------------------------------------------------------------------
# exact host-side communication accounting (paper Table 1, per shard)
# ---------------------------------------------------------------------------


def shard_leaf_volumes(pplan: PopulationPlan) -> Dict[int, Tuple[float, int]]:
    """Per-leaf ``{leaf_index: (scalars sent per member per shard, num_shards)}``
    for a WASH mixing step (bucket 0 is the identity: ``sel·(N-1)/N``)."""
    out = {}
    for info in pplan.infos:
        if info is None:
            continue
        sent = info.sel_local * (pplan.n - 1) / pplan.n
        out[info.index] = (float(sent), info.num_shards)
    return out


def _opt_replay_factor(pplan: PopulationPlan, opt_state) -> int:
    """1 + number of optimizer moment trees the WASH plan is replayed on."""
    if not (pplan.mcfg.shuffles_optimizer() and opt_state is not None):
        return 1
    member = jax.tree_util.tree_unflatten(
        pplan.treedef,
        [jax.ShapeDtypeStruct(i.member_shape, jnp.float32)
         for i in pplan.infos],
    )
    return 1 + len(momentum_like_leaves(opt_state, member))


def static_stage_mix_comm(
    pplan: PopulationPlan,
    stage: int,
    opt_state: Optional[PyTree] = None,
) -> float:
    """Exact scalars sent per member by pipeline stage ``stage`` on a
    mixing-due step, in host float64.

    Stage-split leaves contribute their own stage budget
    (``stage_k_per[stage]·n·(N-1)/N`` per model shard); every other leaf
    is attributed to its owner stage by depth
    (:func:`repro.core.layer_index.stage_of_depth`), so each scalar is
    counted exactly once and
    :func:`static_shard_mix_comm` can report the global volume as the
    literal sum over stages.
    """
    cfg = pplan.mcfg
    if cfg.kind == "none":
        return 0.0
    if stage < 0 or stage >= pplan.num_stages:
        raise ValueError(
            f"stage {stage} out of range for {pplan.num_stages} stages"
        )
    if cfg.kind in ("papa", "papa_all"):
        total = 0
        for info in pplan.infos:
            size = int(np.prod(info.member_shape, dtype=np.int64))
            if info.stage_split:
                lo, hi = info.stage_bounds[stage]
                total += (hi - lo) * (size // info.member_shape[0])
            elif info.stage == stage:
                total += size
        return float(total)
    comm = 0.0
    for info in pplan.infos:
        if info.stage_split:
            sel_s = info.stage_k_per[stage] * pplan.n
            comm += sel_s * (pplan.n - 1) / pplan.n * info.num_shards
        elif info.stage == stage:
            comm += info.sel_local * (pplan.n - 1) / pplan.n * info.num_shards
    return float(comm * _opt_replay_factor(pplan, opt_state))


def static_shard_mix_comm(
    pplan: PopulationPlan,
    opt_state: Optional[PyTree] = None,
) -> float:
    """Exact scalars sent per member on a mixing-due step, summed over the
    member's shards, in host float64 (the multi-axis counterpart of
    :func:`repro.core.mixing.static_mix_comm`; equal to it when no leaf is
    sharded).  Each chip sends ``sel_local·(N-1)/N`` per leaf; a member
    spans ``num_shards`` chips per leaf.  On a pipeline mesh the total is
    the *literal* sum of :func:`static_stage_mix_comm` over the stages, so
    the sum-equals-global contract holds to the last ulp."""
    cfg = pplan.mcfg
    if cfg.kind == "none":
        return 0.0
    if pplan.num_stages > 1:
        return float(sum(
            static_stage_mix_comm(pplan, s, opt_state=opt_state)
            for s in range(pplan.num_stages)
        ))
    if cfg.kind in ("papa", "papa_all"):
        return float(sum(
            int(np.prod(i.member_shape, dtype=np.int64)) for i in pplan.infos
        ))
    comm = sum(
        sent * num for sent, num in shard_leaf_volumes(pplan).values()
    )
    return float(comm * _opt_replay_factor(pplan, opt_state))


# ---------------------------------------------------------------------------
# standalone mixer (public API; repro.launch.dryrun delegates here)
# ---------------------------------------------------------------------------


def make_shardlocal_mixer(
    mesh,
    mcfg: MixingConfig,
    num_blocks: int,
    pop_specs: PyTree,
    opt_specs: PyTree,
):
    """§Perf: a ``shard_map``-wrapped shard-local WASH/PAPA mixing step.

    ``pop_specs`` are the stacked-population specs (leading entry = the
    population axes, remaining entries = the member sharding); member
    specs and the population axes are derived from them, so the caller
    keeps a single source of truth.  Member shapes and the population
    size are read off the population actually passed in (at trace time —
    the planner itself is pure host-side shape math), so one mixer
    factory serves any parameter tree matching ``pop_specs``.

    Returns ``mixer(pop, opt, key) -> (pop, opt, comm_total)``.
    ``comm_total`` is the static scalars-sent count summed over the whole
    population, host-computed (the old dry-run prototype double-counted
    data replicas by psumming a per-chip device scalar over every mesh
    axis — and folded the chip position into every leaf's plan key,
    silently desynchronizing replicas of unsharded leaves).  Because it
    rides the compiled graph it is returned as a float32 device scalar,
    which rounds past 2^24 scalars — callers that need the count exact
    should use :func:`static_shard_mix_comm` host-side, as the fused
    engine does.
    """

    def _strip(spec):
        return P(*tuple(spec)[1:])

    member_specs = jax.tree_util.tree_map(
        _strip, pop_specs, is_leaf=lambda x: isinstance(x, P)
    )
    first = jax.tree_util.tree_flatten(
        pop_specs, is_leaf=lambda x: isinstance(x, P)
    )[0][0]
    lead = tuple(first)[0]
    pop_axes = (lead,) if isinstance(lead, str) else tuple(lead)
    m_pop = 1
    for a in pop_axes:
        m_pop *= int(mesh.shape[a])

    def _global_member_sds(pop_local):
        """Undo the spec slicing: global member shapes from local shards."""
        def one(leaf, spec):
            shape = list(leaf.shape[1:])
            for dim, e in enumerate(tuple(spec) if spec is not None else ()):
                if e is None:
                    continue
                for a in (e,) if isinstance(e, str) else tuple(e):
                    shape[dim] *= int(mesh.shape[a])
            return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

        return jax.tree_util.tree_map(
            one, pop_local, member_specs,
            is_leaf=lambda x: hasattr(x, "shape"),
        )

    def mixer(pop_local, opt_local, key):
        member_tpl = _global_member_sds(pop_local)
        n = jax.tree_util.tree_leaves(pop_local)[0].shape[0] * m_pop
        lids = infer_layer_ids(member_tpl, num_blocks)
        pplan = plan_population_mixing(
            mesh, member_tpl, member_specs, mcfg, lids,
            total_layers(num_blocks), n, pop_axes=pop_axes, dp_axes=(),
        )
        comm = static_shard_mix_comm(pplan)
        if mcfg.shuffles_optimizer() and isinstance(opt_specs, dict):
            comm *= 1 + sum(1 for k in ("mu", "nu") if k in opt_specs)
        new_pop, new_opt = mix_collective_sharded(
            key, pop_local, opt_local, mcfg, pplan, gate=None
        )
        return new_pop, new_opt, jnp.asarray(n * comm, jnp.float32)

    return jax.shard_map(
        mixer,
        mesh=mesh,
        in_specs=(pop_specs, opt_specs, P()),
        out_specs=(pop_specs, opt_specs, P()),
        check_vma=False,
    )
