"""Run one benchmark cell once with `--trace 1`, keeping the program's own
spans, and print its result line with the metrics that read them.

    python3 chipbench/trace_program.py --workload <name> --seed <n> --seconds <s> [--keep <file>]

The run is `run.py`'s `--trace 1` run, with three additions:

- the flattened trace also holds the program's `join.` spans
  (`program_spans.flatten_program`), and its reduction a `program` key
  (`program_spans.reduce_program`: the device's idle time split by
  those spans, their counts and self times), on the window and idle
  gaps that `tracing.reduce_events` computes, worked out again here
  (`window_gaps`) since it does not return them;
- the result line also carries `PROGRAM_METRICS`, read by their files
  in `layer_metrics/`;
- the reduction's `traced_calls` counts the benchmark's `bench.run` and
  `bench.submit` spans of the traced window.

The reduction is logged on standard error (`trace: {...}`), as `run.py`
logs it.  `--keep` writes the flattened events to a JSON file.  The
benchmark's own reduction (`tracing.reduce_events`) keeps only the
benchmark's `bench.` spans, so these metrics are read here until it
keeps the program's too; this tool, with its stand-ins for
`tracing.flatten_xspace`, `tracing.reduce_events` and
`harness.resolve_cell`, goes then (PERF.md, Open questions).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

#: (name, unit) of the per-layer metrics that read the program's spans
PROGRAM_METRICS = (("idle_stage_frac", "ratio"),
                   ("idle_sync_frac", "ratio"),
                   ("idle_dispatch_frac", "ratio"),
                   ("idle_outside_frac", "ratio"),
                   ("verify_pad_frac", "ratio"),
                   ("h2d_bytes_per_row", "B/row"))
CALL_SPANS = ("bench.run", "bench.submit")


def traced_calls(events: list[dict], window: tuple) -> dict:
    """Per benchmark call span, how many start inside the window."""
    lo, hi = window
    return dict(collections.Counter(
        e["name"] for e in events
        if e["name"] in CALL_SPANS and lo <= e["start_ns"] < hi))


def window_gaps(events: list[dict]):
    """`tracing.reduce_events`' traced window (lo, hi) and the first
    device's idle intervals in it; None where it finds no window or no
    op."""
    import tracing
    windows = [e for e in events if e["name"] == tracing.WINDOW_SPAN]
    if not windows:
        return None
    lo = min(e["start_ns"] for e in windows)
    hi = max(e["start_ns"] + e["dur_ns"] for e in windows)
    ops = collections.defaultdict(list)
    for e in events:
        c = tracing._clip([(e["start_ns"], e["start_ns"] + e["dur_ns"])],
                          lo, hi)
        if e["line"] == tracing.OPS_LINE and c:
            ops[e["plane"]] += c
    if not ops:
        return None
    first = tracing.union(ops[sorted(ops)[0]])
    return lo, hi, tracing._gaps(first, lo, hi)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="write the flattened trace events to this file")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import harness
    import program_spans
    import run
    import tracing

    run.T_PROCESS = T_PROCESS
    flatten, reduce = tracing.flatten_xspace, tracing.reduce_events

    def flatten_all(path):
        events = flatten(path) + program_spans.flatten_program(path)
        if args.keep:
            with open(args.keep, "w") as f:
                json.dump({"events": events}, f)
        return events

    def reduce_all(events, top=10):
        out = reduce(events, top)
        if out is not None:
            lo, hi, gaps = window_gaps(events)
            out["program"] = program_spans.reduce_program(events, lo, hi,
                                                          gaps)
            out["traced_calls"] = traced_calls(events, (lo, hi))
        return out

    resolve = harness.resolve_cell

    def resolve_with_program(bench, workload, root=harness.ROOT):
        cell = resolve(bench, workload, root)
        cell.per_layer = cell.per_layer + [
            {"name": n, "unit": u} for n, u in PROGRAM_METRICS]
        return cell

    tracing.flatten_xspace, tracing.reduce_events = flatten_all, reduce_all
    harness.resolve_cell = resolve_with_program
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
