"""Serving traffic: the soup of a seeded population behind the
continuous-batching runtime, driven by the program's request driver.

Set-up makes a two-member population from the seed and averages it with
the program's ``averaged_params`` one leaf at a time (the device holds the
soup and one leaf of each member, never a whole member), builds the
server and its driver, and serves one short request for every
prompt-chunk length the mix will use (and so every decode shape): each
program the window runs is built, or loaded from the persistent cache,
before the window opens.

The window is an open loop: each request is submitted when it is due and
timed from that due time.  Between submissions the loop ticks the driver:
one prompt chunk and one decode step a tick, each decode step ending in
the sampled tokens' readback.  After the window the server is freed, and
the reference reruns each of a sample of finished requests, drawn from the
seed with the longest among them, over its prompt and served tokens.
The architecture (weights, reference, program configuration, counts) is
the module the cell's configuration names.
"""

from __future__ import annotations

import functools
import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, generate
from bench.harness import log, median, percentile
from bench.reference import member_keys
from bench.system import check_layout

#: served tokens the check reads at least (the longest request included)
SAMPLE_TOKENS = 300
#: the reference's sequences are padded to a multiple of this length
REF_BLOCK = 1024


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _member_leaf(keys, arch, a, j):
    """Leaf ``j`` (flatten order) of each member's weights, stacked: the
    members' other leaves are not computed."""
    return jax.vmap(lambda k: jax.tree_util.tree_leaves(
        arch.init_weights(k, a))[j])(keys)


@jax.jit
def _mean_f32(x):
    return jnp.mean(x.astype(jnp.float32), axis=0)


@functools.partial(jax.jit, static_argnames=("arch", "a"))
def _ref_logits(weights, tokens, rows, arch, a):
    return arch.logits_at(weights, tokens, rows, a)


class Driver:
    def __init__(self, cell, seed: int, spans):
        self.cell, self.seed, self.spans = cell, seed, spans
        self.A = cell.architecture
        self.arch = self.A.Arch.from_config(cell.config)
        self.traffic = cell.traffic
        w = cell.workload
        self.page_size, self.slots = w["page_size"], w["max_slots"]
        self.chunk = w["prefill_chunk"]
        self.population = w["population"]
        self.counters: Dict = {}
        self.finished: Dict[int, np.ndarray] = {}

    # -- set-up ----------------------------------------------------------

    def _averaged(self, average):
        """The population's weights averaged one leaf at a time: each
        leaf of every member, stacked, made in one jitted call and passed
        to ``average``; the device holds the averaged leaves made so far
        and one leaf of each member."""
        keys = jnp.stack(member_keys(self.seed, self.population))
        shape = jax.eval_shape(lambda k: self.A.init_weights(k, self.arch),
                               keys[0])
        leaves = []
        for j in range(len(jax.tree_util.tree_leaves(shape))):
            stacked = _member_leaf(keys, self.A, self.arch, j)
            leaves.append(average(stacked))
            del stacked
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(shape), leaves)

    def max_pages(self) -> int:
        longest = max(p + o for p, o in generate.size_set(self.traffic))
        return -(-longest // self.page_size)

    def setup(self, seconds: float) -> None:
        from repro.serving import averaged_params, batching
        from repro.serving.driver import RequestDriver

        a = self.arch
        self.cfg = cfg = self.A.program_config(a, self.cell.entry["config"])
        check_layout(jax.eval_shape(
            lambda k: self.A.init_weights(k, a), jax.random.key(0)), cfg)
        soup = self._averaged(averaged_params)
        mp = self.max_pages()
        self.server = batching.ContinuousServer(
            soup, cfg, mode="soup", page_size=self.page_size,
            max_slots=self.slots, num_pages=self.slots * mp + 1,
            max_pages_per_slot=mp, use_pallas=True, prefill_chunk=self.chunk)
        if not self.server.use_pallas:
            raise RuntimeError("decode does not attend through the Pallas kernel")
        self.driver = RequestDriver(self.server, prefill_chunk=self.chunk)
        self._instrument()
        self._reset_counts()
        warm_start = bool(self.traffic.get("warm_start"))
        self.requests = generate.requests(
            self.traffic, self.seed, seconds, a.vocab_size,
            first=self.slots if warm_start else 0)
        self.times: Dict[int, List[float]] = {}
        self.submitted = 0
        self.failed = 0

        # one request per chunk length the run's prompts use, two tokens
        # each
        rng = np.random.default_rng(self.seed & 0xFFFFFFFF)
        lengths = generate.chunk_lengths(self.requests, self.chunk)
        for i, T in enumerate(lengths):
            self.driver.submit(batching.Request(
                uid=f"warm{i}", tokens=rng.integers(0, a.vocab_size, T,
                                                    dtype=np.int32),
                max_new=2))
        self.driver.drain()
        self.warm_programs = len(lengths)
        log(f"serve: warmed {len(lengths)} prompt-chunk lengths")
        if warm_start:
            # the first server-full of the backlog, prefilled before the
            # window: it opens with every slot decoding
            for r in self.requests[:self.slots]:
                self._submit(r)
            while self.driver._pending or self.driver._prefilling:
                self.driver.tick()
            log(f"serve: {self.slots} requests prefilled before the window")
        self._reset_counts()

    def _submit(self, r) -> None:
        from repro.serving.batching import Request
        from repro.serving.driver import QueueFull

        def on_token(uid, _tok):
            self.times.setdefault(uid, []).append(time.perf_counter())

        def on_finish(uid, result):
            if result is not None:
                self.finished[uid] = np.asarray(result.tokens)

        try:
            self.driver.submit(
                Request(uid=r.uid, tokens=r.prompt, max_new=r.max_new),
                on_token=on_token, on_finish=on_finish)
        except (QueueFull, ValueError) as e:
            self.failed += 1
            log(f"serve: request {r.uid} refused: {e}")
        self.submitted += 1

    def _instrument(self) -> None:
        """The benchmark's spans around the driver's calls into the
        runtime, and the work each call does, counted from its inputs."""
        srv, spans, ps = self.server, self.spans, self.page_size
        A, a = self.A, self.arch

        step, prefill, admit = srv.step, srv._prefill_step, srv._begin_admit

        def timed_step():
            lengths = [s.write_pos + 1 for s in srv._slots if s is not None]
            dmas = flops.paged_attention_dmas(
                [0 if s is None else flops.pages_of(s.write_pos + 1, ps)
                 for s in srv._slots], srv.max_pages)
            t0 = time.perf_counter()
            with spans("bench.decode_step"):
                out = step()
            self.step_s.append(time.perf_counter() - t0)
            self.decode_flops += A.decode_step(a, lengths)
            self.kernel_flops += A.decode_kernel_flops(a, lengths)
            self.kernel_bytes += A.decode_kernel_bytes(a, dmas,
                                                       len(srv._slots), ps)
            self.decode_tokens += len(lengths)
            return out

        def timed_prefill(pf, max_tokens=None):
            T = pf.remaining if max_tokens is None else min(max_tokens,
                                                            pf.remaining)
            self.prefill_flops += A.prefill_chunk(a, pf.pos, T)
            self.prefill_tokens += T
            with spans("bench.prefill_chunk"):
                return prefill(pf, max_tokens)

        def timed_admit(req):
            with spans("bench.admit"):
                return admit(req)

        srv.step, srv._prefill_step, srv._begin_admit = (
            timed_step, timed_prefill, timed_admit)
        spans.wrap(self.driver, "_emit", "bench.emit")

    def _reset_counts(self) -> None:
        self.step_s: List[float] = []
        self.decode_flops = self.prefill_flops = 0
        self.kernel_flops = self.kernel_bytes = 0
        self.decode_tokens = self.prefill_tokens = 0

    # -- the window ------------------------------------------------------

    def window(self, seconds: float) -> None:
        reqs, times = self.requests, self.times
        late: List[float] = []
        i = self.submitted
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while i < len(reqs) and t0 + reqs[i].due <= now:
                self._submit(reqs[i])
                late.append(now - (t0 + reqs[i].due))
                i += 1
            if not self.driver.tick() and i < len(reqs):
                time.sleep(max(0.0, min(1e-3, t0 + reqs[i].due
                                        - time.perf_counter())))
        loop_s = time.perf_counter() - t0

        due = reqs[i - len(late):i]   # due in the window (not the warm start)
        ttft = []
        for r in due:
            ts = times.get(r.uid)
            at = ts[0] if ts else None
            if at is None or at > end:
                ttft.append(end - (t0 + r.due))
            else:
                ttft.append(at - (t0 + r.due))
        half = len(ttft) // 2
        halves = [(percentile(h, 50), percentile(h, 95))
                  for h in (ttft[:half], ttft[half:])]
        gaps, emitted = [], 0
        for ts in times.values():
            inside = [t for t in ts if t0 <= t <= end]
            emitted += len(inside)
            gaps += list(np.diff(inside))
        self.counters = {
            "window_s": seconds, "loop_s": loop_s, "attempted": i,
            "failed": self.failed, "emitted": emitted,
            "finished": len(self.finished),
            "ttft_p95_s": percentile(ttft, 95), "ttft_halves": halves,
            "itl_p95_s": percentile(gaps, 95),
            "late_p95_s": percentile(late, 95), "late_max_s": max(late or [0]),
            "decode_steps": len(self.step_s),
            "decode_step_s": list(self.step_s),
            "decode_flops": self.decode_flops,
            "prefill_flops": self.prefill_flops,
            "kernel_flops": self.kernel_flops,
            "kernel_bytes": self.kernel_bytes,
            "decode_tokens": self.decode_tokens,
            "prefill_tokens": self.prefill_tokens,
        }
        log(f"serve: {len(due)} due, {self.failed} failed, {len(self.finished)} "
            f"finished, {emitted} tokens emitted, {len(self.step_s)} decode "
            f"steps (median {median(self.step_s)}), {self.prefill_tokens} "
            f"prompt tokens; generator late p95 "
            f"{self.counters['late_p95_s']} s, max "
            f"{self.counters['late_max_s']} s")

    def end_to_end(self, chips: int) -> Dict[str, float]:
        c = self.counters
        out = {"serve_tokens_per_s": c["emitted"] / c["window_s"]}
        if c["ttft_p95_s"] is not None:
            out["ttft_p95_ms"] = 1e3 * c["ttft_p95_s"]
        if c["itl_p95_s"] is not None:
            out["itl_p95_ms"] = 1e3 * c["itl_p95_s"]
        return out

    def release(self) -> None:
        self.driver = self.server = None
        gc.collect()

    # -- the check --------------------------------------------------------

    def sample(self) -> List[int]:
        """Finished requests to check, drawn from the seed: the longest
        first, then others until SAMPLE_TOKENS served tokens are read."""
        uids = sorted(self.finished)
        if not uids:
            return []
        by_len = {u: len(self.finished[u]) for u in uids}
        longest = max(uids, key=lambda u: (by_len[u], u))
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 99])
        rest = [u for u in rng.permutation(uids) if u != longest]
        picked, served = [longest], self._served(longest)
        for u in rest:
            if served >= SAMPLE_TOKENS:
                break
            picked.append(int(u))
            served += self._served(int(u))
        return picked

    def _served(self, uid: int) -> int:
        return next(r.max_new for r in self.requests if r.uid == uid)

    def gaps(self, uids: List[int], control: bool = False) -> np.ndarray:
        """For each served token of the sampled requests, the gap between
        the reference's best logit and its logit of the served token
        (``control``: of the token that the architecture's control puts
        first at that position)."""
        A, a = self.A, self.arch
        # the members as the program got them (bf16), averaged in float32
        soup = self._averaged(_mean_f32)
        fwd = functools.partial(_ref_logits, arch=A, a=a)
        ctl = functools.partial(_ref_logits, arch=A, a=A.control(a))
        out = []
        n_rows = self.traffic["output"]["max"]
        for u in uids:
            toks = self.finished[u]
            n = self._served(u)
            S = len(toks) - n
            # inputs padded at the end to a whole number of REF_BLOCKs and
            # rows to the longest answer, so that few shapes are compiled;
            # causal attention keeps the padding out of every real row
            inp = np.zeros(-(-(len(toks) - 1) // REF_BLOCK) * REF_BLOCK,
                           np.int32)
            inp[:len(toks) - 1] = toks[:-1]
            rows = np.full(n_rows, S + n - 2, np.int32)
            rows[:n] = np.arange(S - 1, S + n - 1)
            ref = np.asarray(fwd(soup, inp, rows))[:n]
            if control:
                served = np.asarray(ctl(soup, inp, rows))[:n].argmax(-1)
            else:
                served = toks[S:]
            out.append(ref.max(-1) - ref[np.arange(n), served])
        return np.concatenate(out) if out else np.zeros(0)

    @staticmethod
    def numbers(gaps: np.ndarray) -> Dict[str, float]:
        """What the check can compare of the served tokens' gaps: their
        mean, their widest, and the share of tokens that are not the
        reference's best."""
        return {"mean_logit_gap": float(np.mean(gaps)),
                "logit_gap": float(np.max(gaps)),
                "not_best_share": float(np.mean(gaps > 0))}

    def check(self, control: bool = False) -> List:
        """The numbers the cell's ``limits`` name, over the sampled
        requests' served tokens (``control``: the int8 control's)."""
        uids = self.sample()
        if not uids:
            raise RuntimeError("no request finished in the window")
        got = self.numbers(self.gaps(uids, control))
        self.check_info = dict(got, requests=len(uids),
                               tokens=sum(self._served(u) for u in uids))
        log(f"serve: checked {len(uids)} requests, "
            f"{self.check_info['tokens']} served tokens; widest gap "
            f"{got['logit_gap']}, not the best {got['not_best_share']}")
        limits = self.cell.workload["limits"]
        return [(k, got[k], lim) for k, lim in limits.items()]
