#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 101,102,...

On the chip, in one process: the cell is set up once, then for each seed
one window at the cell's own load runs through the same harness code as
a benchmark run. Once every window has run and the program's state is
freed, the same sample of served requests of each is compared with
the plain reference (the program's reading) and read by the float8
control (the control's reading: the reference one precision step below
the configuration's, in the program's place). Both readings go through
the harness's one decision, which has to find the program correct and
the control not. One JSON line per seed, then a summary: the lower
reading (the largest the program gives) and the upper (the smallest the
control gives).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from bench import harness
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), args.workload)
    srv = harness.setup(cell)
    wins = [(int(s), harness.measure(srv, int(s), args.seconds, False))
            for s in args.seeds.split(",")]
    # as in a run, the program's state goes before the reference runs
    srv.engine = None
    gc.unfreeze()
    gc.collect()
    rows = []
    for seed, win in wins:
        gaps = harness.compare(cell, win.served, seed, control=True)
        program = harness.decide(cell, win.served, srv.model,
                                 float(gaps["gap"].max()))
        control = harness.decide(cell, win.served, srv.model,
                                 float(gaps["control_gap"].max()),
                                 label="control")
        row = {"seed": seed, "window_s": win.window_s,
               "peak_bytes": win.peak_bytes,
               "end_to_end": harness.end_to_end(cell, win),
               "correct": program.pop("ok"),
               "control_correct": control.pop("ok"),
               "check": program, "control": control}
        rows.append(row)
        print(json.dumps(row), flush=True)
    lower = max(r["check"]["max_logit_gap"]["value"] for r in rows)
    upper = min(r["control"]["max_logit_gap"]["value"] for r in rows)
    print(json.dumps({"summary": True, "seeds": len(rows),
                      "all_correct": all(r["correct"] for r in rows),
                      "control_never_correct": not any(
                          r["control_correct"] for r in rows),
                      "lower": lower, "upper": upper,
                      "ratio": upper / lower if lower > 0 else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
