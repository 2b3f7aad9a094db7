"""The one generator of serving traffic, driven by a traffic file.

A mix is a fixed multiset of request sizes and arrival gaps, the same for
every seed: sizes are quantiles of the file's clipped lognormal laws, at
``distinct_sizes`` evenly spaced levels, and arrival gaps are quantiles of
the exponential law at the file's rate.  The seed only orders them and
draws the prompts' tokens, so runs on different seeds do the same work.

Traffic file keys (``bench/traffic/<name>.json``)::

    driver          "serve"
    arrivals        "backlog" (all due at 0) or "poisson" (open loop)
    rate            requests/s of a poisson mix
    requests        size of a backlog (a poisson mix draws as many as
                    its rate brings in the window, and a fifth more)
    distinct_sizes  quantile levels of the length laws
    prompt, output  {"median", "sigma", "min", "max"} in tokens
    warm_start      a backlog whose first server-full of requests is
                    admitted and prefilled before the window opens, so
                    that the window starts with every slot decoding; those
                    requests take evenly spaced levels, the same for every
                    seed
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def lognormal_levels(law: Dict, k: int) -> List[int]:
    """``k`` quantiles of a lognormal law clipped to [min, max]."""
    out = []
    for i in range(k):
        z = _NORMAL.inv_cdf((i + 0.5) / k)
        v = law["median"] * math.exp(law["sigma"] * z)
        out.append(int(min(max(round(v), law["min"]), law["max"])))
    return out


def size_set(traffic: Dict) -> List[tuple]:
    """The (prompt, output) pairs of the mix's levels, paired by a fixed
    permutation (so a long prompt is not always given a long answer)."""
    k = traffic["distinct_sizes"]
    prompts = lognormal_levels(traffic["prompt"], k)
    outputs = lognormal_levels(traffic["output"], k)
    order = np.random.default_rng(12345).permutation(k)
    return [(prompts[i], outputs[order[i]]) for i in range(k)]


def spread_levels(n: int, k: int) -> List[int]:
    """``n`` indices spread evenly over ``range(k)``: floor((i + 0.5) k / n)."""
    return [int((i + 0.5) * k / n) for i in range(n)]


def request_count(traffic: Dict, seconds: float) -> int:
    if traffic["arrivals"] == "backlog":
        return int(traffic["requests"])
    return int(math.ceil(traffic["rate"] * seconds * 1.2)) + 1


def requests(traffic: Dict, seed: int, seconds: float, vocab: int,
             first: int = 0) -> List[Req]:
    """The requests of one run, sorted by due time.  Their size levels
    are spread evenly over all the mix's levels, whatever their number:
    request i of n takes level floor((i + 0.5) k / n), so every run holds
    the law's tail.  The ``first`` of them are an evenly spaced pick of
    those (the same for every seed), in an order drawn from the seed; the
    rest follow in an order drawn from the seed."""
    n = request_count(traffic, seconds)
    sizes = size_set(traffic)
    k = len(sizes)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    levels = spread_levels(n, k)
    head = [levels[i] for i in spread_levels(first, n)]
    # the rest in turn through the levels (0, 1, .., 0, 1, ..), less the
    # head's
    rank: Dict[int, int] = {}
    turn = []
    for v in levels:
        turn.append((rank.get(v, 0), v))
        rank[v] = rank.get(v, 0) + 1
    rest = [v for _, v in sorted(turn)]
    for h in head:
        rest.remove(h)
    order = ([head[j] for j in rng.permutation(len(head))]
             + [rest[j] for j in rng.permutation(len(rest))])
    if traffic["arrivals"] == "backlog":
        dues = [0.0] * n
    else:
        rate = float(traffic["rate"])
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        gaps = [gaps[j] for j in rng.permutation(n)]
        dues = list(np.cumsum(gaps))
    out = []
    for uid, j in enumerate(order):
        s, m = sizes[j]
        out.append(Req(uid, float(dues[uid]),
                       rng.integers(0, vocab, size=s, dtype=np.int32), m))
    return out


def chunk_lengths(reqs: List[Req], chunk: int) -> List[int]:
    """Every prompt-chunk length the prompts of ``reqs`` cut into."""
    lengths = set()
    for r in reqs:
        s = len(r.prompt)
        if s >= chunk:
            lengths.add(chunk)
        if s % chunk:
            lengths.add(s % chunk)
    return sorted(lengths)
