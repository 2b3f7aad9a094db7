"""Parameter shuffling — the core mechanism of WASH (paper Eq. 3).

Two implementations, equal in expectation (Eq. 4) and both exactly
distance-preserving (Eq. 5):

``dense``    Faithful to the paper's math: every scalar coordinate draws an
             independent uniform permutation of {1..N} (argsort of per-scalar
             uniforms over the ens axis) gated by an independent
             Bernoulli(p_l).  Used for validation and CPU-scale repro.

``bucketed`` TPU-native: exactly k_l = round(p_l * d_l) coordinates are
             selected per leaf via stratified sampling (unique, shared
             randomness), split into N equal buckets; bucket s applies the
             cyclic shift π(n) = (n+s) mod N.  Bucket 0 is the identity, so
             each member *sends* exactly k_l*(N-1)/N scalars per leaf per
             step — the paper's p·d communication volume — and the exchange
             lowers to static-shape ``collective-permute`` ops on the ICI
             when executed under ``shard_map`` (see :func:`bucketed_apply_collective`).

Shuffles are expressed as *plans* (index pytrees) built once per step from
shared randomness, so WASH+Opt can replay the identical plan on the
optimizer state (paper §4 "Training methods").
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.schedules import layer_probability, layer_probability_array

PyTree = Any


# ---------------------------------------------------------------------------
# dense (faithful) mode
# ---------------------------------------------------------------------------


def dense_plan(key: jax.Array, shape, n: int, p_l: float):
    """Per-coordinate uniform permutation + Bernoulli gate for one leaf.

    ``shape`` is the *member* shape (without the ens axis).  Returns
    ``(perm, mask)`` with ``perm: (n, *shape) int32`` columns being
    independent uniform permutations of range(n) and ``mask: shape bool``.
    """
    kp, km = jax.random.split(key)
    u = jax.random.uniform(kp, (n,) + tuple(shape))
    perm = jnp.argsort(u, axis=0).astype(jnp.int32)
    mask = jax.random.bernoulli(km, p=jnp.float32(p_l), shape=tuple(shape))
    return perm, mask


def dense_apply(leaf: jax.Array, perm: jax.Array, mask: jax.Array) -> jax.Array:
    """θ̂_n^i = θ_{π_i(n)}^i where masked, else θ_n^i (leaf: (n, *shape))."""
    shuffled = jnp.take_along_axis(leaf, perm, axis=0)
    return jnp.where(mask[None], shuffled, leaf)


def dense_plan_layered(key: jax.Array, shape, n: int, p_vec):
    """Dense plan for a stacked-blocks leaf: shape = (L, *rest).

    ``p_vec`` gives the Eq. 6 probability per scanned layer, so the
    layer-wise schedule stays exact even when all blocks live in one leaf.
    """
    kp, km = jax.random.split(key)
    u = jax.random.uniform(kp, (n,) + tuple(shape))
    perm = jnp.argsort(u, axis=0).astype(jnp.int32)
    p = jnp.asarray(p_vec, jnp.float32).reshape((shape[0],) + (1,) * (len(shape) - 1))
    mask = jax.random.uniform(km, tuple(shape)) < p
    return perm, mask


# ---------------------------------------------------------------------------
# bucketed (TPU-native) mode
# ---------------------------------------------------------------------------


def stratified_unique_indices(key: jax.Array, d: int, k: int) -> jax.Array:
    """k unique indices in [0, d), one uniform draw per equal stratum.

    Deterministically unique (strata are disjoint) with uniform marginal
    coverage — a static-shape, sort-free surrogate for sampling without
    replacement, chosen for TPU friendliness.  The returned order is
    randomly permuted so position within the plan carries no information.
    """
    if k <= 0:
        return jnp.zeros((0,), jnp.int32)
    ko, ks = jax.random.split(key)
    i = jnp.arange(k)
    starts = (i * d) // k
    ends = ((i + 1) * d) // k
    widths = jnp.maximum(ends - starts, 1)
    offs = jax.random.randint(ko, (k,), 0, jnp.iinfo(jnp.int32).max) % widths
    idx = (starts + offs).astype(jnp.int32)
    return jax.random.permutation(ks, idx)


def bucket_count(d: int, n: int, p_l: float) -> int:
    """Per-bucket coordinate count k_per; total selected = k_per * n."""
    k = int(round(p_l * d))
    return max(k // n, 0)


def bucketed_plan(
    key: jax.Array, d: int, n: int, p_l: float, k_per: Optional[int] = None
) -> Optional[jax.Array]:
    """Index plan ``(n, k_per) int32``; row s holds coordinates shifted by s.

    Returns None when the leaf is too small / probability too low for even
    one coordinate per bucket (no communication for this leaf this step).
    ``k_per`` overrides the count derived from ``p_l`` — the shard-local
    planner (:mod:`repro.core.shardplan`) passes each shard's slice of the
    *global* budget so per-shard volumes never exceed the global plan's.
    """
    if k_per is None:
        k_per = bucket_count(d, n, p_l)
    if k_per == 0:
        return None
    idx = stratified_unique_indices(key, d, k_per * n)
    return idx.reshape(n, k_per)


def bucketed_plan_layered(
    key: jax.Array, num_layers: int, d_rest: int, n: int, p_vec,
    counts=None,
) -> Optional[jax.Array]:
    """Bucketed plan for a stacked-blocks leaf of member shape (L, d_rest).

    Layer l contributes round(p_l * d_rest) coordinates inside its own flat
    range [l*d_rest, (l+1)*d_rest); counts are static (p_vec is static), so
    the concatenated index set keeps Eq. 6's depth profile exactly while
    remaining a single static-shape plan.  The pooled set is randomly
    permuted, trimmed to a multiple of N and reshaped to (N, k_per).

    ``counts`` overrides the per-layer coordinate counts (pre-clip); the
    shard-local planner passes each shard's slice of the global per-layer
    budget, with ``d_rest`` then being the *local* per-layer flat size.
    """
    if counts is None:
        counts = [int(round(float(p_vec[l]) * d_rest)) for l in range(num_layers)]
    pieces = []
    for l in range(num_layers):
        k_l = int(counts[l])
        if k_l <= 0:
            continue
        kl_key = jax.random.fold_in(key, l)
        idx_l = stratified_unique_indices(kl_key, d_rest, min(k_l, d_rest))
        pieces.append(idx_l + l * d_rest)
    if not pieces:
        return None
    idx = jnp.concatenate(pieces)
    k_per = idx.shape[0] // n
    if k_per == 0:
        return None
    idx = jax.random.permutation(jax.random.fold_in(key, num_layers + 1), idx)
    return idx[: k_per * n].reshape(n, k_per)


def bucketed_apply_stacked(leaf: jax.Array, idx: jax.Array) -> jax.Array:
    """Apply a bucketed plan to a stacked leaf (n, *shape) — no collectives."""
    n = leaf.shape[0]
    flat = leaf.reshape(n, -1)
    for s in range(1, n):
        vals = flat[:, idx[s]]
        # θ̂_n = θ_{(n+s) mod N}: member n takes member (n+s)'s value.
        flat = flat.at[:, idx[s]].set(jnp.roll(vals, -s, axis=0))
    return flat.reshape(leaf.shape)


def bucketed_apply_collective(
    x_flat: jax.Array, idx: jax.Array, axis_name: str
) -> jax.Array:
    """Apply a bucketed plan to one member's flat params under shard_map.

    Each bucket is a single ``ppermute``: member j sends its k_per selected
    scalars to member (j-s) mod N (equivalently: everyone receives from its
    (n+s)-th neighbour).  Total send volume per member per step:
    k_per * (N-1) scalars = p·d·(N-1)/N — the paper's Table 1 accounting.
    """
    n = lax.axis_size(axis_name)
    out = x_flat
    for s in range(1, n):
        vals = x_flat[idx[s]]
        recv = lax.ppermute(
            vals, axis_name, perm=[(j, (j - s) % n) for j in range(n)]
        )
        out = out.at[idx[s]].set(recv)
    return out


def _block_from(vals: jax.Array, axis_name, q: int, m: int) -> jax.Array:
    """This shard's copy of the block held q shards ahead on the ring."""
    if q % m == 0:
        return vals
    return lax.ppermute(
        vals, axis_name, perm=[(j, (j - q) % m) for j in range(m)]
    )


def bucketed_apply_collective_blocked(
    x_flat: jax.Array, idx: jax.Array, axis_name
) -> jax.Array:
    """Bucketed apply for a shard holding ``n_local`` contiguous members.

    ``x_flat``: (n_local, D); the global population is n = n_local * m
    (m = mesh axis size).  Bucket s applies the global cyclic shift
    θ̂_g = θ_{(g+s) mod n}.  For member i of shard j (global g = j*n_local+i)
    the source rows [g+s, g+s+n_local) span at most two neighbouring
    shards, so each bucket costs ≤ 2 static ``ppermute`` ops regardless of
    n_local.  Degenerate cases recover the existing paths exactly:
    m == 1 → jnp.roll (the stacked reference), n_local == 1 → the
    per-member :func:`bucketed_apply_collective`.
    """
    m = lax.axis_size(axis_name)
    n_local = x_flat.shape[0]
    n = n_local * m
    out = x_flat
    for s in range(1, n):
        vals = out[:, idx[s]]                       # (n_local, k_per)
        q, r = divmod(s, n_local)
        recv1 = _block_from(vals, axis_name, q, m)
        if r == 0:
            shifted = recv1
        else:
            recv2 = _block_from(vals, axis_name, q + 1, m)
            shifted = jnp.concatenate([recv1, recv2], axis=0)[r : r + n_local]
        out = out.at[:, idx[s]].set(shifted)
    return out


# ---------------------------------------------------------------------------
# tree-level plans
# ---------------------------------------------------------------------------


def make_plan(
    key: jax.Array,
    params: PyTree,
    layer_ids: PyTree,
    total_layers: int,
    base_p: float,
    schedule: str = "decreasing",
    mode: str = "dense",
    n: Optional[int] = None,
) -> PyTree:
    """Build a shuffle plan for a whole (stacked) population pytree.

    ``params`` may be the stacked population (leading ens axis) or a single
    member template together with explicit ``n``.
    """
    import numpy as np

    # ints and np.ndarrays are both ordinary pytree leaves, so layer_ids
    # flattens in lockstep with params.
    leaves, treedef = jax.tree_util.tree_flatten(params)
    lid_leaves = jax.tree_util.tree_flatten(layer_ids)[0]
    plans = []
    for i, (leaf, lid) in enumerate(zip(leaves, lid_leaves)):
        k = jax.random.fold_in(key, i)
        if n is None:
            nn, member_shape = int(leaf.shape[0]), leaf.shape[1:]
        else:
            nn, member_shape = n, leaf.shape
        layered = not isinstance(lid, int)
        if layered:
            p_vec = np.clip(
                layer_probability_array(base_p, lid, total_layers, schedule), 0.0, 1.0
            )
            if p_vec.max() <= 0.0:
                plans.append(None)
                continue
            assert member_shape and len(p_vec) == member_shape[0], (
                f"layered lid len {len(p_vec)} vs leaf {member_shape}"
            )
            if mode == "dense":
                plans.append(dense_plan_layered(k, member_shape, nn, p_vec))
            elif mode == "bucketed":
                d_rest = int(np.prod(member_shape[1:], dtype=np.int64)) if len(member_shape) > 1 else 1
                plans.append(
                    bucketed_plan_layered(k, int(member_shape[0]), d_rest, nn, p_vec)
                )
            else:
                raise ValueError(f"unknown shuffle mode {mode!r}")
            continue
        p_l = layer_probability(base_p, int(lid), total_layers, schedule)
        if p_l <= 0.0:
            plans.append(None)
        elif mode == "dense":
            plans.append(dense_plan(k, member_shape, nn, min(p_l, 1.0)))
        elif mode == "bucketed":
            d = 1
            for s in member_shape:
                d *= int(s)
            plans.append(bucketed_plan(k, d, nn, min(p_l, 1.0)))
        else:
            raise ValueError(f"unknown shuffle mode {mode!r}")
    return jax.tree_util.tree_unflatten(treedef, plans)


def _bucketed_apply_pallas(leaf: jax.Array, idx: jax.Array) -> jax.Array:
    """Stacked bucketed apply through the fused Pallas kernel (one VMEM
    pass instead of N-1 roll/scatter rounds).  Pure data movement, so the
    result is bitwise-identical to :func:`bucketed_apply_stacked`;
    ``interpret=None`` auto-detects TPU vs interpret mode."""
    from repro.kernels.wash_shuffle import bucketed_shuffle_pallas

    n = leaf.shape[0]
    flat = leaf.reshape(n, -1)
    return bucketed_shuffle_pallas(flat, idx).reshape(leaf.shape)


def apply_plan_stacked(
    plan: PyTree, tree: PyTree, mode: str = "dense", use_pallas: bool = False
) -> PyTree:
    """Apply a plan to a stacked pytree (params, or optimizer moments).

    ``use_pallas`` routes bucketed applies through the fused Pallas kernel
    (:func:`repro.kernels.wash_shuffle.bucketed_shuffle_pallas`)."""

    def _one(p, leaf):
        if p is None:
            return leaf
        if mode == "dense":
            perm, mask = p
            return dense_apply(leaf, perm, mask)
        if use_pallas:
            return _bucketed_apply_pallas(leaf, p)
        return bucketed_apply_stacked(leaf, p)

    return jax.tree_util.tree_map(
        _one, plan, tree, is_leaf=lambda x: x is None or isinstance(x, tuple)
    )


def apply_plan_collective(plan: PyTree, tree: PyTree, axis_name: str) -> PyTree:
    """Apply a bucketed plan to one member's pytree under shard_map."""

    def _one(p, leaf):
        if p is None:
            return leaf
        flat = leaf.reshape(-1)
        return bucketed_apply_collective(flat, p, axis_name).reshape(leaf.shape)

    return jax.tree_util.tree_map(_one, plan, tree, is_leaf=lambda x: x is None)


def apply_plan_collective_blocked(
    plan: PyTree, tree: PyTree, axis_name, use_pallas: bool = False
) -> PyTree:
    """Apply a bucketed plan to a block of members under shard_map.

    ``tree`` leaves carry a leading local-ens axis (n_local, *member_shape);
    the plan was built for the population held along ``axis_name`` (a mesh
    axis name or tuple of names), so every shard applies the same indices
    and the cross-shard rows travel by ``ppermute``.

    ``use_pallas`` routes the apply through the fused Pallas kernel when
    the population axis is a single shard (the 1-device degenerate case,
    where the blocked apply is exactly the stacked roll); multi-shard
    exchanges always take the ``ppermute`` path — the kernel is a local
    HBM-pass optimization, not a collective.
    """
    pallas_ok = use_pallas and lax.axis_size(axis_name) == 1

    def _one(p, leaf):
        if p is None:
            return leaf
        if pallas_ok:
            return _bucketed_apply_pallas(leaf, p)
        n_local = leaf.shape[0]
        flat = leaf.reshape(n_local, -1)
        return bucketed_apply_collective_blocked(flat, p, axis_name).reshape(
            leaf.shape
        )

    return jax.tree_util.tree_map(_one, plan, tree, is_leaf=lambda x: x is None)


# ---------------------------------------------------------------------------
# communication accounting (paper Table 1)
# ---------------------------------------------------------------------------


def plan_selected_scalars(plan: PyTree, mode: str = "dense"):
    """Scalars *selected* for shuffling this step (paper's p·d accounting)."""
    total = 0
    for p in jax.tree_util.tree_leaves(
        plan, is_leaf=lambda x: x is None or isinstance(x, tuple)
    ):
        if p is None:
            continue
        if mode == "dense":
            _, mask = p
            total = total + jnp.sum(mask)
        else:
            total = total + p.size
    return total


def plan_sent_scalars(plan: PyTree, n: int, mode: str = "dense"):
    """Scalars actually *sent* per member (identity assignments excluded)."""
    sel = plan_selected_scalars(plan, mode)
    return sel * (n - 1) / n
