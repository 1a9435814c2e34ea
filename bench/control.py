"""Readings that set the limits of the check: the program against the
reference, and what must fail against the reference, on several seeds.

    python3 bench/control.py --workload d1.fresh --seconds 20 --seeds 11 12 13

For each seed this makes one short run of the cell (set-up, a window at the
cell's own load, the wait for the jobs in flight), all in this one process,
which holds the chip.  After each window the sampled jobs are checked as
every run checks them, by the reference, and then again with the reference
put in the program's place in two more modes (``reference.MODES``): the
control, computed in bfloat16, and the fault ``frozen``, whose training
steps leave the state unchanged.  One JSON line per seed gives the three
sets of numbers; the last line gives, per number, the largest program
reading and the smallest reading of each mode.  The benchmark's own runs
never run these modes.
"""
from __future__ import annotations

import argparse
import json
import sys

import run

MODES = ("control", "frozen")


def readings(workload: str, seeds, seconds: float) -> dict:
    other = {}

    def checks(rec, seed, mode="reference"):
        other[seed] = {m: run_checks(rec, seed, m) for m in MODES}
        return run_checks(rec, seed, mode)

    run_checks = run.check_outputs
    run.check_outputs = checks
    out = {"program": {}, **{m: {} for m in MODES}}
    try:
        for seed in seeds:
            res = run.run_cell(workload, seed, seconds, False)
            line = {"seed": seed, "attempted": res["attempted"],
                    "program": {k: c["value"]
                                for k, c in res["checks"].items()},
                    "faults": res["checks"]["faults"]["what"]}
            for m in MODES:
                line[m] = {k: c["value"] for k, c in other[seed][m].items()}
            print(json.dumps(line), flush=True)
            for k, v in line["program"].items():
                out["program"][k] = max(out["program"].get(k, v), v)
            for m in MODES:
                for k, v in line[m].items():
                    out[m][k] = min(out[m].get(k, v), v)
    finally:
        run.check_outputs = run_checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    print(json.dumps(readings(args.workload, args.seeds, args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
