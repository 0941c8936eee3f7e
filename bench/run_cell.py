#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout: builds the cell's configuration with random
weights from ``--seed``, warms every shape its traffic uses (set-up),
serves ``--seconds`` of open-loop traffic through
``TryageEngine.serve``, checks what the window produced against the
plain references, and prints the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics from a profiler trace of the
window (``--trace 1``).  The last line of standard output is one JSON
object; each number compared for ``correct`` is printed beside its
limit as the last lines of standard error and under ``checks``, the
last key of that object.

Off a TPU, with fewer chips than the cell asks for, on a device kind
the peaks table (``bench/flops.py``) lacks, or without the program's
``src/`` beside ``bench/``, it exits non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root=ROOT, require_tpu=True, peaks=None,
         t_start=T_START, fault=None) -> dict:
    """One run; returns the result object it printed.  The test suite
    calls it with ``require_tpu=False`` and ``peaks`` to drive a run
    on the CPU, and ``fault`` (a callable given the built ``System``)
    to break the timed path underneath."""
    args = parse(argv)
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.program_path(ROOT)
    cell = harness.Cell.load(root, args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if require_tpu:
        devs, peaks = harness.require_devices(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]

    system = harness.System(cell.cfg, args.seed)
    if fault is not None:
        fault(system)
    n_programs = system.warm(cell.cascade)
    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
    run, traffic, results, dup, compiles = harness.serve_window(
        system, cell, args.seconds, args.seed, trace_dir, t_start, peaks)
    mem = harness.memory_peak(devs)

    read = harness.readings(system, traffic, results, args.seed)
    checks = harness.check(len(traffic.requests), results, dup, read,
                           cell.limits)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    breakdown = None
    if args.trace:
        from bench import trace
        run.trace = trace.Reduction(trace.load(trace.find_xspace(trace_dir)))
        device.update(busy_s=run.trace.busy_s(), window_s=run.trace.window_s)
        names = [e["name"] for e in cell.cfg["experts"]]
        breakdown = run.trace.breakdown(label=lambda s: (
            f"flush:{names[s.stat('expert')]}:b{s.stat('bucket')}"
            if s.name == "bench.flush" else s.name[len("bench."):]))
    metrics = {}
    for m in cell.metrics(bool(args.trace)):
        value = harness.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    lat = run.latency_s
    info = {"requests_due": len(traffic.requests),
            "results": len(results), "programs_warmed": n_programs,
            "compiles_in_window": compiles,
            "lateness_ms": {"p50": 1e3 * float(_pct(run.lateness, 50)),
                            "max": 1e3 * float(_max(run.lateness))},
            "latency_ms": {"p50": 1e3 * float(_pct(lat, 50)),
                           "p95": 1e3 * float(_pct(lat, 95)),
                           "p99": 1e3 * float(_pct(lat, 99))},
            "counters": run.stats, "readings": read}
    if run.trace is not None:
        info["idle_s_by_span"] = run.trace.idle_by_span()
    print("run: " + json.dumps(info, default=str), flush=True)

    out = {"correct": correct, "attempted": len(traffic.requests),
           "failed": checks["missing_failed_or_duplicate"]["value"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


def _pct(x, q):
    import numpy as np
    return np.percentile(x, q) if len(x) else float("nan")


def _max(x):
    return max(x) if len(x) else float("nan")


if __name__ == "__main__":
    main()
