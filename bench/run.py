#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``. The run sets up the served model (weights from the
configuration's seed, packed as GQSA), warms up the shapes the cell's
traffic reaches, measures for ``--seconds``, compares a sample of what it
served with the plain reference, and prints one JSON object as the last
line of standard output. With ``--trace 0`` its metrics are the cell's
end-to-end metrics; with ``--trace 1`` the per-layer metrics read from a
profiler trace of the window. Without a TPU (or with fewer chips than the
cell asks for) it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from bench import harness
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        cell = harness.load_cell(json.load(f), args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
