"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of `workloads` in
`BENCHMARK.json`.  The run draws its corpus and query pool from `--seed`,
builds the join plan (fitting the filter), warms up every shape of its
traffic, measures a closed loop for `--seconds`, then checks every
answer of the window against the plain reference.  With `--trace 0` the
result carries the cell's end-to-end metrics; with `--trace 1` the
profiler covers the window's first seconds and the result carries the
per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is the result, one JSON object; the
numbers the check compared, each with its limit, are also the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, the run prints no result and exits with 1.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.seed < 0:
        print("run.py: --seed must be a non-negative whole number",
              file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sys.path.insert(0, BENCH_DIR)
    import harness
    cell = harness.resolve_cell(bench, args.workload)

    import jax
    # No persistent compilation cache: the program pins R row-major on the
    # TPU (`engine._put_row_major`), and an executable read back from the
    # cache expects the default column-major R, so every hit fails
    # ("expected parameter ... {0,1:T(8,128)} but got ... {1,0:T(8,128)}").
    # Every run compiles its programs in set-up instead.
    jax.config.update("jax_enable_compilation_cache", False)
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    harness.log(f"device: {json.dumps(info)}")
    if info["platform"] != "tpu":
        harness.log(f"run.py: no TPU found (JAX platform "
                    f"{info['platform']!r}); nothing was measured")
        return 1
    if info["count"] < cell.chips:
        harness.log(f"run.py: the cell asks for {cell.chips} chips, JAX "
                    f"sees {info['count']}")
        return 1
    from peaks import peaks_for
    peaks_for(info["kind"])             # an unknown device is an error

    sys.path.insert(0, os.path.join(ROOT, "src"))
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, device_kind=info["kind"])
    info["memory_peak_bytes"] = out.pop("_peak_bytes")
    trace = out.pop("_trace", None)
    if trace is not None:
        info["busy_s"], info["window_s"] = trace["busy_s"], trace["window_s"]
        harness.log(f"trace: {json.dumps(trace)}")
    checks = out.pop("checks")
    out["device"] = info
    out["checks"] = checks
    for name, c in checks.items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
