"""What the plain reference of every architecture shares: the members' keys
from the seed, float32 helpers (RMSNorm, rotary embeddings) and the WASH
shuffle's plan and apply over any leaf tree.

Nothing here imports the program under test.  Each architecture's weights
and forward pass are in ``bench/architectures/<name>.py``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def member_keys(seed: int, n: int):
    """The keys of ``n`` members drawn from ``seed`` (one per member)."""
    base = jax.random.fold_in(jax.random.key(0), seed % (1 << 31))
    base = jax.random.fold_in(base, seed >> 31)
    return [jax.random.fold_in(base, m) for m in range(n)]


# ---------------------------------------------------------------------------
# float32 helpers
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotate-half rotary embedding of x: (T, heads, hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def to_f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


# ---------------------------------------------------------------------------
# the WASH shuffle (bucketed plan, paper Eq. 3 with shared randomness)
# ---------------------------------------------------------------------------


def _stratified(key, d: int, k: int):
    ko, ks = jax.random.split(key)
    i = jnp.arange(k)
    starts, ends = (i * d) // k, ((i + 1) * d) // k
    offs = jax.random.randint(ko, (k,), 0, jnp.iinfo(jnp.int32).max) % \
        jnp.maximum(ends - starts, 1)
    return jax.random.permutation(ks, (starts + offs).astype(jnp.int32))


def leaf_depths(params, num_layers: int) -> List:
    """Depth of each leaf (flatten order): the embedding 0, block i at
    i + 1 (stacked leaves get one depth per layer), the rest the last."""
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        top = path[0].key
        if top == "embed":
            out.append(0)
        elif top == "blocks":
            out.append(np.arange(1, num_layers + 1))
        else:
            out.append(num_layers + 1)
    return out


def wash_plan(key, member, num_layers: int, n: int, base_p: float):
    """Per-leaf (n, k) index plans of one WASH step: k coordinates per
    bucket s; bucket s moves member (m + s) mod n's value to member m.
    Probabilities fall linearly with depth to 0 at the last layer."""
    last = num_layers + 1
    plans = []
    for i, (leaf, depth) in enumerate(zip(jax.tree_util.tree_leaves(member),
                                          leaf_depths(member, num_layers))):
        k = jax.random.fold_in(key, i)
        if isinstance(depth, np.ndarray):
            d_rest = int(np.prod(leaf.shape[1:]))
            pieces = []
            for l, dl in enumerate(depth):
                k_l = int(round(base_p * (1.0 - dl / last) * d_rest))
                if k_l > 0:
                    pieces.append(_stratified(jax.random.fold_in(k, l), d_rest,
                                              min(k_l, d_rest)) + l * d_rest)
            if not pieces:
                plans.append(None)
                continue
            idx = jnp.concatenate(pieces)
            per = idx.shape[0] // n
            idx = jax.random.permutation(jax.random.fold_in(k, num_layers + 1),
                                         idx)
            plans.append(idx[:per * n].reshape(n, per) if per else None)
        else:
            p = base_p * (1.0 - depth / last)
            per = int(round(p * leaf.size)) // n if p > 0 else 0
            plans.append(_stratified(k, leaf.size, per * n).reshape(n, per)
                         if per else None)
    return plans


def wash_apply(members: List, plans) -> List:
    """Apply one step's plans to a list of member trees: in bucket s,
    member m takes member (m + s) mod n's value."""
    n = len(members)
    flat = [jax.tree_util.tree_leaves(m) for m in members]
    treedef = jax.tree_util.tree_structure(members[0])
    out = [list(f) for f in flat]
    for j, idx in enumerate(plans):
        if idx is None:
            continue
        vecs = [f[j].reshape(-1) for f in flat]
        new = list(vecs)
        for s in range(1, n):
            for m in range(n):
                new[m] = new[m].at[idx[s]].set(vecs[(m + s) % n][idx[s]])
        # buckets hold disjoint coordinates, so their order is immaterial
        for m in range(n):
            out[m][j] = new[m].reshape(flat[m][j].shape)
    return [jax.tree_util.tree_unflatten(treedef, o) for o in out]
