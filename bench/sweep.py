#!/usr/bin/env python3
"""Find a configuration's knee on the chip: the highest steady open-loop
rate it sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 8 \
        --rates 100,200,400,800

Builds the cell's configuration once, warms it, and serves one window
per rate with the cell's traffic mix made steady (one phase at that
rate, the mix's prompts, flags and confidence floor kept).  Prints one
JSON line per rate: offered and completed rates, latency percentiles
from due time, the generator's lateness and the requests still
unanswered when the window closed.  It stops after the first window
that completes under 95% of the offered rate.  Exits non-zero off a
TPU.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import numpy as np

    from bench import harness
    harness.program_path(ROOT)
    cell = harness.Cell.load(ROOT, args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs, peaks = harness.require_devices(cell.chips)
    system = harness.System(cell, args.seed)
    t = time.monotonic()
    n = system.warm(cell.cascade)
    print(json.dumps({"setup_s": time.monotonic() - T_START,
                      "warm_s": time.monotonic() - t, "programs": n}),
          flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.mix = dict(cell.mix, phases=[{"seconds": 0, "rate_rps": rate}])
        run, traffic, results, dup, compiles = harness.serve_window(
            system, cell, args.seconds, args.seed + i, None, T_START, peaks)
        ok = np.isfinite(run.done) & (run.done <= run.close)
        due = run.due <= run.close
        lat = run.latency_s
        row = {"rate_rps": rate, "due": int(due.sum()),
               "completed_rps": float(ok.sum()) / args.seconds,
               "open_at_close": int((due & ~ok).sum()),
               "p50_ms": 1e3 * float(np.percentile(lat, 50)),
               "p95_ms": 1e3 * float(np.percentile(lat, 95)),
               "p99_ms": 1e3 * float(np.percentile(lat, 99)),
               "lateness_max_ms": 1e3 * float(run.lateness.max()),
               "compiles_in_window": compiles, "stats": run.stats}
        print(json.dumps(row, default=str), flush=True)
        system.reseed(args.seed + i + 1)
        if row["completed_rps"] < 0.95 * rate:
            break
    print(json.dumps({"memory_peak_bytes": harness.memory_peak(devs)}))


if __name__ == "__main__":
    main()
