"""The traffic generator: every seed gets the same sizes and arrival
gaps, in another order."""

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generate  # noqa: E402
from bench.harness import load_json  # noqa: E402

#: an open-loop chat mix: heavy-tailed prompts and answers
CHAT = {
    "driver": "serve", "arrivals": "poisson", "rate": 1.0,
    "distinct_sizes": 64,
    "prompt": {"median": 512, "sigma": 1.0, "min": 32, "max": 4096},
    "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
}
LONGDOC = load_json(ROOT / "bench" / "traffic" / "serve.longdoc.json")


def test_same_work_for_every_seed():
    a = generate.requests(CHAT, 1, 30.0, 1000)
    b = generate.requests(CHAT, 2**33 + 5, 30.0, 1000)
    sizes = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)  # noqa: E731
    assert sizes(a) == sizes(b)
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due for r in rs]), 9))  # noqa: E731
    assert gaps(a) == gaps(b)
    assert [r.uid for r in a] != [r.uid for r in b] or \
        [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_same_seed_same_requests():
    a = generate.requests(LONGDOC, 7, 30.0, 1000)
    b = generate.requests(LONGDOC, 7, 30.0, 1000)
    assert all((x.due, x.max_new) == (y.due, y.max_new)
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(r.due == 0.0 for r in a) and len(a) == LONGDOC["requests"]


def test_lengths_follow_the_clipped_lognormal():
    for mix in (CHAT, LONGDOC):
        for law in (mix["prompt"], mix["output"]):
            levels = generate.lognormal_levels(law, mix["distinct_sizes"])
            assert levels == sorted(levels)
            assert law["min"] <= levels[0] and levels[-1] <= law["max"]
            mid = levels[len(levels) // 2 - 1:len(levels) // 2 + 1]
            assert min(mid) <= law["median"] <= max(mid)


def test_poisson_rate_and_chunk_lengths():
    reqs = generate.requests(CHAT, 3, 40.0, 1000)
    span = reqs[-1].due - reqs[0].due
    assert abs(len(reqs) / span - CHAT["rate"]) / CHAT["rate"] < 0.05
    lengths = generate.chunk_lengths(reqs, 128)
    cut = set()
    for r in reqs:
        n = len(r.prompt)
        cut |= {128} if n >= 128 else set()
        cut |= {n % 128} if n % 128 else set()
    assert cut <= set(lengths)


@pytest.mark.parametrize("seconds", [5.0, 30.0, 200.0])
def test_every_run_holds_the_laws_tail(seconds):
    """However few requests a run draws, their levels are spread evenly
    from the first to the last: the top 1/n of the law is always in."""
    k = CHAT["distinct_sizes"]
    sizes = generate.size_set(CHAT)
    reqs = generate.requests(CHAT, 2**33 + 11, seconds, 1000)
    n = len(reqs)
    levels = [int((i + 0.5) * k / n) for i in range(n)]
    assert sorted(len(r.prompt) for r in reqs) == sorted(
        sizes[j][0] for j in levels)
    assert max(levels) >= k * (1 - 1 / n) - 1
    if n >= k:
        assert max(len(r.prompt) for r in reqs) == CHAT["prompt"]["max"]


def test_backlog_head_is_spread_and_rest_covers_every_level():
    reqs = generate.requests(LONGDOC, 5, 30.0, 1000, first=16)
    k = LONGDOC["distinct_sizes"]
    sizes = generate.size_set(LONGDOC)
    level = lambda r: sizes.index((len(r.prompt), r.max_new))  # noqa: E731
    assert sorted(map(level, reqs[:16])) == [int((i + 0.5) * k / 16)
                                              for i in range(16)]
    assert set(map(level, reqs[16:])) == set(range(k))
