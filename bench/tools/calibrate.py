"""Readings that the limits of ``correct`` are set from.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--fault half_batch]

For each seed, in one process: the cell's set-up and a short window, as a
run makes them; then every number the check can compare for the program
against the reference, and the same numbers for the control (the
reference at its architecture's ``control``, the nearest lower
precision), each side with ``correct`` as a run decides it under the
cell's limits.  ``--fault`` plants a fault in the program (training:
``half_batch``, each member's loss over half its rows) and reads the
program's numbers only.  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import system  # noqa: E402,F401  (puts the program on the path)


def plant(fault: str) -> None:
    from repro.models import transformer as M

    if fault == "half_batch":
        real = M.loss_fn

        def half(params, cfg, batch):
            tokens = batch["tokens"]
            return real(params, cfg, {"tokens": tokens[:tokens.shape[0] // 2]})

        M.loss_fn = half
    else:
        raise SystemExit(f"unknown fault {fault!r}")


def numbers(drv, control: bool) -> dict:
    """The program's numbers and, with ``control``, the control's, each
    with ``correct`` as a run decides it under the cell's limits."""
    limits = drv.cell.workload["limits"]
    judge = lambda got: all(got[k] <= v for k, v in limits.items())  # noqa: E731
    if hasattr(drv, "gaps"):
        uids = drv.sample()
        out = drv.numbers(drv.gaps(uids))
        out.update(requests=len(uids), correct=judge(out),
                   tokens=sum(drv._served(u) for u in uids))
        if control:
            ctl = drv.numbers(drv.gaps(uids, control=True))
            out.update({f"control_{k}": v for k, v in ctl.items()})
            out["control_correct"] = judge(ctl)
        return out
    from bench.drivers.train import rel_errors

    ref = drv.reference()
    out = drv.compare(drv.readings, ref)
    out["correct"] = judge(out)
    out["losses"] = drv.readings["losses"]
    out["ref_losses"] = ref["losses"]
    out["leaves"] = drv.leaf_names
    for k in ("grad_norms", "delta_norms"):
        out[f"program_{k}"] = drv.readings[k].tolist()
        out[f"ref_{k}"] = ref[k].tolist()
    out["program_grad_errs"] = rel_errors(drv.readings["grad_sample"],
                                          ref["grad_sample"]).tolist()
    if control:
        ctl = drv.reference(control=True)
        got = drv.compare(ctl, ref)
        out.update({f"control_{k}": v for k, v in got.items()})
        out["control_correct"] = judge(got)
        out["control_grad_errs"] = rel_errors(ctl["grad_sample"],
                                              ref["grad_sample"]).tolist()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault")
    args = ap.parse_args()
    cell = H.find_cell(args.workload)
    H.require_chips(cell.chips)
    H.enable_compile_cache()
    if args.fault:
        plant(args.fault)
    mod = H.driver_module(cell.traffic["driver"])
    drv = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if drv is not None and hasattr(drv, "start"):
            drv.start(seed)        # training: the same compiled chunk
        else:
            drv = mod.Driver(cell, seed, H.Spans(False))
            drv.setup(args.seconds)
        drv.window(args.seconds)
        drv.release()
        out = numbers(drv, control=not args.fault)
        out.update(seed=seed, fault=args.fault,
                   seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
