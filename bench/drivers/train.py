"""Training traffic: the fused WASH engine's chunk, driven back to back.

Set-up builds one object, the compiled chunk with its population and
optimizer state, made from the seed.  It drives that object through its
first three steps (one step a call, through the same executable and feed
the window uses), reading what the check compares, then through one whole
chunk, which warms the window's call.  The window dispatches whole chunks,
one queued ahead, each timed to the engine's own loss readback.

After the window the program's state is freed and the reference follows
the first three steps in float32 from the same seed: the loss of each
step, each leaf's first gradient as the optimizer gets it (SGD's momentum
after one step is the gradient plus weight decay: its norm, and a fixed
sample of its elements kept on the host since set-up), and each leaf's
change after three steps, weights held in the configured dtype between
steps as the program holds them.  The architecture (weights, reference,
program configuration, counts) is the module the cell's configuration
names; it has to provide ``loss``.
"""

from __future__ import annotations

import functools
import gc
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops
from bench.harness import BenchError, log
from bench.reference import member_keys, to_f32, wash_apply, wash_plan
from bench.system import check_layout

#: steps the check follows
CHECK_STEPS = 3
#: elements of each leaf's first gradient that the check compares one by
#: one: every (size // GRAD_SAMPLE)-th, the same for every seed
GRAD_SAMPLE = 1 << 16


def leaf_norms(tree) -> jax.Array:
    """(n, leaves) f32 norms of each member's leaves of a stacked tree."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)).reshape(
            x.shape[0], -1), axis=1))
        for x in jax.tree_util.tree_leaves(tree)], axis=1)


def leaf_samples(tree) -> List[jax.Array]:
    """(n, <= GRAD_SAMPLE) f32 elements of each member's leaves of a
    stacked tree, at a fixed stride."""
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        flat = x.reshape(x.shape[0], -1)
        stride = max(1, flat.shape[1] // GRAD_SAMPLE)
        out.append(flat[:, ::stride][:, :GRAD_SAMPLE].astype(jnp.float32))
    return out


def rel_errors(prog, ref) -> np.ndarray:
    """(n, leaves) ‖prog - ref‖ / ‖ref‖ of each member's sampled leaves."""
    return np.stack([
        np.linalg.norm(np.asarray(p, np.float64) - np.asarray(r, np.float64),
                       axis=1)
        / np.maximum(np.linalg.norm(np.asarray(r, np.float64), axis=1),
                     1e-30)
        for p, r in zip(prog, ref)], axis=1)


def gap(prog: np.ndarray, ref: np.ndarray, keep: Optional[np.ndarray] = None
        ) -> float:
    """Worst leaf of |‖prog‖ - ‖ref‖| over max(‖ref‖, the median leaf's)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is not None:
        prog, ref = prog[:, keep], ref[:, keep]
    floor = np.median(ref, axis=1, keepdims=True)
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, floor)))


# -- the reference's programs (float32, ``highest`` matmuls) ---------------


_copy = jax.jit(lambda tree: jax.tree_util.tree_map(jnp.copy, tree))


@jax.jit
def _zeros_f32(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


@functools.partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(0, 1))
def _ref_step(theta, mu, tokens, arch, a, hyper):
    """One SGD-with-momentum step of one member.  The weights come in and
    go out in the configured dtype, as the program stores them: an output
    in that dtype makes the rounding certain, where a round trip through
    it inside the program may be simplified away."""
    lr, momentum, wd = hyper
    th32 = to_f32(theta)
    lval, g = jax.value_and_grad(lambda th: arch.loss(th, tokens, a))(th32)
    mu = jax.tree_util.tree_map(lambda m, g_, th: momentum * m + g_ + wd * th,
                                mu, g, th32)
    theta = jax.tree_util.tree_map(
        lambda th, m, t: (th - lr * m).astype(t.dtype), th32, mu, theta)
    return lval, theta, mu


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(1,))
def _ref_shuffle(key, members, a, base_p):
    return wash_apply(members, wash_plan(key, members[0], a.num_layers,
                                         len(members), base_p))


@jax.jit
def _member_norms(tree):
    return leaf_norms(jax.tree_util.tree_map(lambda x: x[None], tree))[0]


@jax.jit
def _member_samples(tree):
    return [x[0] for x in leaf_samples(
        jax.tree_util.tree_map(lambda x: x[None], tree))]


@jax.jit
def _member_delta(theta, theta0):
    return _member_norms(jax.tree_util.tree_map(
        lambda x, t: x.astype(jnp.float32) - t.astype(jnp.float32),
        theta, theta0))


class Driver:
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.A = cell.architecture
        if not hasattr(self.A, "loss"):
            raise BenchError(f"architecture {self.A.__name__} has no loss: "
                             f"it cannot train")
        self.arch = self.A.Arch.from_config(cell.config)
        w = cell.workload
        self.n = w["population"]
        self.batch, self.seq = cell.traffic["batch"], cell.traffic["seq_len"]
        self.chunk_steps = w["record_every"]
        self.lr, self.base_p = w["lr"], w["base_p"]
        self.momentum, self.wd = w["momentum"], w["weight_decay"]
        self.counters: Dict = {}
        self.readings: Dict = {}

    # -- the object the window drives -----------------------------------

    def setup(self, seconds: float) -> None:
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

        from repro.core.layer_index import infer_layer_ids, total_layers
        from repro.core.mixing import MixingConfig
        from repro.models import transformer as M
        from repro.optim import make_optimizer
        from repro.train import engine as T

        A, a, n = self.A, self.arch, self.n
        cfg = A.program_config(a, self.cell.entry["config"])
        member = self.member = jax.eval_shape(lambda k: A.init_weights(k, a),
                                              jax.random.key(0))
        check_layout(member, cfg)
        self.leaf_names = [jax.tree_util.keystr(p) for p, _ in
                           jax.tree_util.tree_flatten_with_path(member)[0]]
        mesh = jax.make_mesh((1,), ("ens",), axis_types=(AxisType.Auto,),
                             devices=jax.devices()[:1])
        opt_init, opt_update = make_optimizer(
            "sgd", momentum=self.momentum, weight_decay=self.wd)
        mcfg = MixingConfig(kind="wash", base_p=self.base_p, mode="bucketed",
                            pallas_shuffle=True)
        spec = lambda tree: jax.tree_util.tree_map(lambda _: P("ens"), tree)  # noqa: E731
        pop_shape = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), member)
        opt_shape = jax.eval_shape(jax.vmap(opt_init), pop_shape)
        pspec, ospec = spec(pop_shape), spec(opt_shape)
        shard = lambda specs: jax.tree_util.tree_map(  # noqa: E731
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        def init_state(theta):
            pop = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape), theta)
            return pop, jax.vmap(opt_init)(pop)

        # one program makes the initial weights everywhere they are used
        # (the state, the change after three steps, the reference), so
        # that they are the same bits: two programs that draw them can
        # round an element differently
        self.init_theta = jax.jit(lambda k: A.init_weights(k, a))
        self.init_state = jax.jit(
            init_state, out_shardings=(shard(pspec), shard(ospec)))
        loss = lambda p, b: M.loss_fn(p, cfg, b)[0]  # noqa: E731
        self.step_fn = T.make_fused_chunk_fn(
            mesh, mcfg, infer_layer_ids(member, a.num_layers),
            total_layers(a.num_layers), opt_update, loss, pspec, ospec,
            {"tokens": P(None, "ens")}, with_mixing=True, use_pallas=True)
        R_, V = self.chunk_steps, a.vocab_size
        self.make_batch = jax.jit(
            lambda k, c: {"tokens": jax.random.randint(
                jax.random.fold_in(k, c), (R_, n, self.batch, self.seq), 0, V,
                jnp.int32)},
            out_shardings={"tokens": NamedSharding(mesh, P(None, "ens"))})
        rep = NamedSharding(mesh, P())
        self.rep = rep
        self.lrs = jax.device_put(np.full((R_,), self.lr, np.float32), rep)
        self.gates = jax.device_put(np.ones((R_,), np.float32), rep)
        self.n_valid = {k: jax.device_put(np.int32(k), rep)
                        for k in (1, self.chunk_steps)}
        self.norms = jax.jit(leaf_norms)
        self.samples = jax.jit(leaf_samples)
        self.delta_norms = jax.jit(lambda pop, theta0: leaf_norms(
            jax.tree_util.tree_map(
                lambda x, t: x.astype(jnp.float32) - t.astype(jnp.float32)[None],
                pop, theta0)))
        self.keydata = jax.jit(
            lambda k, s: jax.vmap(lambda t: jax.random.key_data(
                jax.random.fold_in(k, t)))(s), out_shardings=rep)
        self.start(self.seed)

    def start(self, seed: int) -> None:
        """Make the state from ``seed`` and drive the first CHECK_STEPS
        steps, one a call, then one whole chunk, through the window's own
        call; the programs stay as they were built."""
        self.seed = seed
        key = self.key = member_keys(seed, 1)[0]
        self.data_key = jax.random.fold_in(key, 1)
        self.mix_key = jax.random.fold_in(key, 2)
        self.pop, self.opt = self.init_state(self.init_theta(key))
        self.step = 0
        self.batches = 0
        losses = []
        for t in range(CHECK_STEPS):
            losses.append(self._call(1))
            if t == 0:
                self.readings["grad_norms"] = np.asarray(
                    self.norms(self.opt["mu"]))
                self.readings["grad_sample"] = [
                    np.asarray(x) for x in self.samples(self.opt["mu"])]
        self.readings["losses"] = losses
        self.readings["delta_norms"] = np.asarray(
            self.delta_norms(self.pop, self.init_theta(key)))
        log(f"train: first losses {losses}")
        self._call(self.chunk_steps)

    def _dispatch(self, n_valid: int):
        """Dispatch one call of the chunk; returns its loss (on device)."""
        steps = list(range(self.step, self.step + n_valid))
        steps += [steps[-1]] * (self.chunk_steps - n_valid)
        batch = self.make_batch(self.data_key, self.batches)
        self.batches += 1
        kd = self.keydata(self.mix_key, np.asarray(steps, np.int32))
        with self.spans("bench.train_dispatch"):
            self.pop, self.opt, loss = self.step_fn(
                self.pop, self.opt, batch, self.lrs, kd, self.gates,
                self.n_valid[n_valid])
        self.step += n_valid
        return loss

    def _call(self, n_valid: int) -> float:
        loss = self._dispatch(n_valid)
        with self.spans("bench.train_readback"):
            return float(loss)

    # -- the window ------------------------------------------------------

    def window(self, seconds: float) -> None:
        steps, losses = 0, []
        t0 = time.perf_counter()
        inflight = [self._dispatch(self.chunk_steps)]
        while inflight:
            if time.perf_counter() - t0 < seconds:
                inflight.append(self._dispatch(self.chunk_steps))
            with self.spans("bench.train_readback"):
                losses.append(float(inflight.pop(0)))
            steps += self.chunk_steps
        window_s = time.perf_counter() - t0
        if not all(np.isfinite(losses)):
            raise RuntimeError(f"non-finite loss in the window: {losses}")
        tokens = steps * self.n * self.batch * self.seq
        a = self.arch
        self.counters = {
            "window_s": window_s, "steps": steps, "tokens": tokens,
            "flops": steps * self.n * self.A.train_step(
                a, self.batch, self.seq),
            "shuffle_bytes": steps * flops.shuffle_step_bytes(
                self.member, a.num_layers, self.n, self.base_p),
            "last_loss": losses[-1],
        }
        log(f"train: {steps} steps in {window_s:.3f} s, last loss "
            f"{losses[-1]:.4f}")

    def end_to_end(self, chips: int) -> Dict[str, float]:
        c = self.counters
        return {"train_tokens_per_s": c["tokens"] / c["window_s"] / chips}

    def release(self) -> None:
        del self.pop, self.opt
        gc.collect()

    # -- the check --------------------------------------------------------

    def reference(self, control: bool = False) -> Dict:
        """The reference's readings of the first CHECK_STEPS steps;
        ``control``: the architecture's control in its place."""
        A, a, n = self.A, self.arch, self.n
        if control:
            a = A.control(a)
        hyper = (self.lr, self.momentum, self.wd)
        theta0 = self.init_theta(self.key)
        members = [_copy(theta0) for _ in range(n)]
        mus = [_zeros_f32(theta0) for _ in range(n)]
        losses, grad_norms, grad_sample = [], None, None
        for t in range(CHECK_STEPS):
            toks = self.make_batch(self.data_key, t)["tokens"][0]
            out = [_ref_step(members[m], mus[m], toks[m], A, a, hyper)
                   for m in range(n)]
            losses.append(float(np.mean([float(o[0]) for o in out])))
            members = [o[1] for o in out]
            mus = [o[2] for o in out]
            if t == 0:
                grad_norms = np.stack([np.asarray(_member_norms(mu))
                                       for mu in mus])
                per_member = [[np.asarray(x) for x in _member_samples(mu)]
                              for mu in mus]
                grad_sample = [np.stack(xs) for xs in zip(*per_member)]
            members = _ref_shuffle(jax.random.fold_in(self.mix_key, t),
                                   members, a, self.base_p)
        del mus
        delta = np.stack([np.asarray(_member_delta(m, theta0))
                          for m in members])
        return {"losses": losses, "grad_norms": grad_norms,
                "grad_sample": grad_sample, "delta_norms": delta}

    def compare(self, prog: Dict, ref: Dict) -> Dict[str, float]:
        """The numbers the check can compare: the worst step's relative
        loss gap; the worst leaf's gap of first-gradient norms and of
        three-step-change norms (``gap``); and the first gradient compared
        element by element on each leaf's sample, ‖prog - ref‖ / ‖ref‖,
        at the worst leaf (``grad_err``).  Leaves whose reference gradient
        is under a thousandth of the median leaf's are left out of the
        change and of the element-wise error."""
        loss_gap = max(abs(p - r) / abs(r)
                       for p, r in zip(prog["losses"], ref["losses"]))
        g = np.asarray(ref["grad_norms"])
        keep = np.min(g, axis=0) >= 1e-3 * np.median(g)
        err = rel_errors(prog["grad_sample"], ref["grad_sample"])[:, keep]
        return {
            "loss_gap": float(loss_gap),
            "grad_gap": gap(prog["grad_norms"], ref["grad_norms"]),
            "delta_gap": gap(prog["delta_norms"], ref["delta_norms"], keep),
            "grad_err": float(np.max(err)),
        }

    def check(self, control: bool = False) -> List:
        """The numbers the cell's ``limits`` name (``control``: the
        control's, in the program's place)."""
        ref = self.reference()
        got = self.compare(self.reference(control=True) if control
                           else self.readings, ref)
        self.ref_readings = ref
        limits = self.cell.workload["limits"]
        return [(k, got[k], lim) for k, lim in limits.items()]
