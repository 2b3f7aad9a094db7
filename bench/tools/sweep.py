"""Find the knee of a serving cell: the highest arrival rate it sustains.

    python3 bench/tools/sweep.py --workload <cell> --rates 0.5,1,1.5 \
        --seconds 40 --seed 11

For each rate, in one process, the cell's server and driver serve the
cell's traffic at that rate for ``--seconds``.  Each line gives, per
rate, the requests due and finished, the output tokens/s, the TTFT
percentiles of the first and of the second half of the due requests, and
the backlog at the close (requests due but not yet decoding).  A rate the
system sustains ends with a small backlog and second-half TTFTs like the
first half's; above the knee both grow with the window.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import system  # noqa: E402,F401


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    base = H.find_cell(args.workload)
    H.require_chips(base.chips)
    H.enable_compile_cache()
    from bench.drivers.serve import Driver

    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, traffic=dict(base.traffic, rate=rate))
        drv = Driver(cell, args.seed, H.Spans(False))
        drv.setup(args.seconds)
        drv.window(args.seconds)
        c = drv.counters
        backlog = len(drv.driver._pending) + len(drv.driver._prefilling)
        print(json.dumps({
            "rate": rate, "due": c["attempted"], "finished": c["finished"],
            "tokens_per_s": c["emitted"] / c["window_s"],
            "ttft_p50_first_half_s": c["ttft_halves"][0][0],
            "ttft_p95_first_half_s": c["ttft_halves"][0][1],
            "ttft_p50_second_half_s": c["ttft_halves"][1][0],
            "ttft_p95_second_half_s": c["ttft_halves"][1][1],
            "itl_p95_s": c["itl_p95_s"], "backlog": backlog,
            "decode_step_median_s": H.median(c["decode_step_s"]),
        }), flush=True)
        drv.release()


if __name__ == "__main__":
    main()
