#!/usr/bin/env python3
"""Readings for the output check's limits, on the chip: the program's
and its control's, over many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        --seconds 6

Builds the cell once; for each seed swaps in that seed's weights, serves
a short window of the cell's own traffic, and prints one JSON line with
the numbers ``harness.check`` compares, read twice on the same sample:
for the program (the lower readings) and for the control, the plain
reference put in the program's place one precision below what the
configuration states, every matrix product of the experts and the
router in float8 with one scale per tensor (the upper readings).  ``bench/limits/<config>.json`` is set
between the two.  Exits non-zero off a TPU.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.program_path(ROOT)
    cell = harness.Cell.load(ROOT, args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    _, peaks = harness.require_devices(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    system = harness.System(cell.cfg, seeds[0])
    system.warm(cell.cascade)
    for i, seed in enumerate(seeds):
        if i:
            system.reseed(seed)
        _, traffic, results, dup, _ = harness.serve_window(
            system, cell, args.seconds, seed, None, T_START, peaks)
        row = {"seed": seed, "due": len(traffic.requests),
               "results": len(results), "duplicates": dup,
               "failed": sum(r.failed for r in results.values()),
               "program": harness.readings(system, traffic, results, seed),
               "control": harness.readings(system, traffic, results, seed,
                                           expert_prec="fp8",
                                           router_prec="fp8")}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
