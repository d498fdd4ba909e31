"""The check's control: the plain reference, computed one precision step
below the configuration's, put in the program's place, and the faults a
cell can have, each read by the cell's own check.

    python3 chipbench/control.py --workload <name> --seeds 1 2 3 [--precisions high bf16]

For each seed it draws the cell's corpus and query pool as a run does
and builds the cell's plan as a run does (fitting the filter).  Every
pool set goes once through the plan's `run` (the program's answers) and
once through the route's own filter stage (`searched_mask`: which
queries the program sends to verify).  The control answers a query with
the reference at the control precision where the program verifies it
and with 0 where the program skips it, so it stands exactly where verify
stands.  The faults alter the program's own answers, keeping their
shape: `half_zeroed` answers 0 for the second half of each set, as a
verify or compaction that dropped rows would; `answer_altered` adds 1
to every 50th answer.  Each set of answers goes through the cell's check
against the reference at the configuration's precision; one JSON line
per seed and set of answers gives the numbers compared.  `program_off`
counts the program's answers that differ from the reference masked by
`searched_mask` (0 where the mask is the one verify saw).  Benchmark
runs never run this.  Like `run.py` it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def half_zeroed(counts: np.ndarray) -> np.ndarray:
    out = np.array(counts)
    out[len(out) // 2:] = 0
    return out


def answer_altered(counts: np.ndarray) -> np.ndarray:
    out = np.array(counts)
    out[::50] += 1
    return out


FAULTS = {"half_zeroed": half_zeroed, "answer_altered": answer_altered}


def control_readings(cell, seed: int, precisions) -> list:
    """[{seed, answers, <check numbers>, ...}] for one seed: the
    program's own answers, the control at each of `precisions` in the
    program's place, and each fault of `FAULTS`."""
    import jax
    import harness
    from corpus import draw
    from repro.core.engine import clear_program_cache

    route = harness.route_module(cell.traffic["route"])
    rows, eps = int(cell.traffic["rows"]), float(cell.config["eps"])
    R, Q = draw(cell.config, seed, int(cell.traffic["pool"]), rows)
    pool = harness.Pool(Q, rows)
    t0 = time.perf_counter()
    plan = route.build(cell.config, R, seed, {})
    starts = range(0, pool.n, rows)
    searched = np.concatenate([route.searched_mask(plan, pool.rows_at(k, rows),
                                                   eps) for k in starts])
    program = np.concatenate([np.asarray(plan.run(pool.rows_at(k, rows),
                                                  eps).counts)
                              for k in starts])
    t_program = time.perf_counter() - t0
    del plan
    clear_program_cache()
    jax.clear_caches()
    gc.collect()
    record = harness.RunRecord(cell=cell, calls=[], window_s=0.0,
                               setup_s=0.0, compile_s=0.0, spans={},
                               peak_bytes=None, device_kind="")
    record.expected = harness.reference_counts(cell.config, pool, R)

    def reading(name: str, got: np.ndarray, seconds: float) -> dict:
        calls = [harness.Call(start=k, n=rows, t0=0.0, counts=got[k:k + rows])
                 for k in starts]
        return {"seed": seed, "answers": name,
                **harness.check_counts(route, calls, record),
                "queries": pool.n, "searched": int(searched.sum()),
                "differing_counts": int((got != record.expected).sum()),
                "seconds": seconds}

    out = [dict(reading("program", program, t_program),
                program_off=int((program != np.where(
                    searched, record.expected, 0)).sum()))]
    for p in precisions:
        t0 = time.perf_counter()
        got = harness.reference_counts(cell.config, pool, R, precision=p)
        out.append(reading(p, np.where(searched, got, 0),
                           time.perf_counter() - t0))
    for name, fault in FAULTS.items():
        got = np.concatenate([fault(program[k:k + rows]) for k in starts])
        out.append(reading(name, got, 0.0))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precisions", nargs="+", default=["high", "bf16"])
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.resolve_cell(json.load(f), args.workload)
    import jax
    # As in run.py: the program's row-major pin of R breaks the cache.
    jax.config.update("jax_enable_compilation_cache", False)
    if jax.devices()[0].platform != "tpu":
        harness.log("control.py: no TPU found; nothing was measured")
        return 1
    for seed in args.seeds:
        for line in control_readings(cell, seed, args.precisions):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
