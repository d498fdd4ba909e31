"""Ring sweep schedule rows (DESIGN.md §15): overlapped vs serial.

`RingSharded(overlap=True)` issues the next query block's `ppermute`
BEFORE the current histogram step and combines partial counts with a
ring reduce-scatter, so the hop transfers while the MXU sweeps.  These
rows pin the schedule's cost envelope on the CPU container: at ``r=1``
the overlapped program compiles to zero collectives, so it must be no
slower than serial; at ``r>=2`` the overlapped schedule should win (on
CPU the win is the removed `[r, q, m]` buffer + full-buffer `psum` +
`take`; on TPU/GPU the transfer itself also hides —
`launch/xla_flags.py`).

Off the CPU each (r_shards) cell runs IN-PROCESS over the first r real
devices (r up to the device count): a chip belongs to one process, so
a child started after this process touched JAX could not reach it.  On
a CPU host each cell runs in a SUBPROCESS with
``--xla_force_host_platform_device_count=<r>`` (XLA reads the flag once
at backend init, so the parent process cannot host the multi-device
mesh itself).  Either way `_time_schedules` pre-stages padded device
inputs once and times the COMPILED sweep program (`hist_program`) with
`block_until_ready` —
the schedule is the thing under test, and the engine entry point's
per-call host glue (padding, `device_put`, readback; measured by the
xjoin suite) would bury the tens-of-microseconds schedule delta.  The
two schedules are timed in INTERLEAVED rounds so machine drift cancels
instead of biasing whichever ran second, and their counts are asserted
bit-identical before timing.

  ``ring/range_count-{overlap|serial}-r{r}`` -> us/query, with the
  overlap rows' derived column carrying ``speedup_vs_serial`` — the
  BENCH_<n> acceptance number (>= ~1.0 at r=1, > 1.0 at r>=2).

Runs at a fixed smoke shape regardless of REPRO_BENCH_SCALE (the
schedule comparison, not the scale, is the point): R sized to one
block_r tile per shard at r=2 — the communication-visible regime the
overlap targets (on a pod the per-device shard is exactly the "small
enough to hop every step" size; a compute-saturated shard hides ANY
schedule equally well and measures nothing).
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import emit, save_json

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
R_SHARD_COUNTS = (1, 2)
NR, NQ, D, M = 1024, 512, 32, 16
WARM, ROUNDS = 5, 50

def _time_schedules(r: int) -> dict[str, float]:
    """{schedule: total_ms} of the compiled ring sweep on an
    `(r, data=1)` join mesh over the first r local devices: BEST of the
    timing rounds — scheduler interference on a shared host only ever
    adds time, so the one-sided noise makes min the faithful cost of the
    compiled schedule."""
    import time

    import jax.numpy as jnp
    import numpy as np

    import repro.core.engine as em
    from repro.core.engine import JoinEngine
    from repro.core.topology import RingSharded
    from repro.launch.mesh import make_join_mesh

    rng = np.random.default_rng(0)

    def unit(n):
        x = rng.normal(size=(n, D)).astype(np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    R, Q = unit(NR), unit(NQ)
    eps = np.linspace(0.3, 1.2, M).astype(np.float32)
    mesh = make_join_mesh(data=1, r=r)
    runs, base = {}, None
    for schedule, overlap in (("overlap", True), ("serial", False)):
        eng = JoinEngine(R, "cosine", backend="jnp", mesh=mesh,
                         topology=RingSharded(overlap=overlap))
        got = np.asarray(eng.range_count_hist(Q, eps))
        if base is None:
            base = got
        else:
            np.testing.assert_array_equal(got, base)
        prog = em._hist_program(eng.mesh, eng.data_axis, eng.backend,
                                eng.metric, eng.block_q, eng.block_r,
                                eng.eps_chunk, eng.nr, eng.topology)
        qdev = eng._put_q(eng._pad_q(Q))
        epdev = jnp.asarray(eng._pad_eps(eps))
        runs[schedule] = (prog, qdev, eng._Rdev, epdev, eng._nrv_dev)
    samples = {k: [] for k in runs}
    for _ in range(WARM + ROUNDS):
        for schedule, (prog, *args) in runs.items():
            t0 = time.perf_counter()
            prog(*args).block_until_ready()
            samples[schedule].append(time.perf_counter() - t0)
    return {k: float(np.min(np.array(ts[WARM:]))) * 1e3
            for k, ts in samples.items()}


#: child script (CPU host): force r host devices, then time in the
#: child; prints ``RING_ROW,<schedule>,<ms>`` lines
_CHILD = """
from repro.launch.xla_flags import apply_xla_flags, host_device_count_flag
apply_xla_flags(host_device_count_flag({r}))
from benchmarks.bench_ring import _time_schedules
for schedule, ms in _time_schedules({r}).items():
    print(f"RING_ROW,{{schedule}},{{ms:.4f}}", flush=True)
"""


def _child_rows(r: int) -> dict[str, float]:
    """{schedule: total_ms} from one forced-`r`-device subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _CHILD.format(r=r)],
                         env=env, cwd=REPO_ROOT, capture_output=True,
                         text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"bench_ring child (r={r}) failed:\n"
                           + out.stderr[-3000:])
    rows: dict[str, float] = {}
    for line in out.stdout.splitlines():
        parts = line.split(",")
        if parts[0] == "RING_ROW":
            rows[parts[1]] = float(parts[2])
    if set(rows) != {"overlap", "serial"}:
        raise RuntimeError(f"bench_ring child (r={r}) emitted {set(rows)}:\n"
                           + out.stdout[-2000:])
    return rows


def run() -> list:
    import jax
    in_process = jax.default_backend() != "cpu"
    rows = []
    for r in R_SHARD_COUNTS:
        if in_process and r > len(jax.devices()):
            continue
        ms = _time_schedules(r) if in_process else _child_rows(r)
        speedup = ms["serial"] / max(ms["overlap"], 1e-9)
        for schedule in ("overlap", "serial"):
            derived = (f"speedup_vs_serial={speedup:.3f}"
                       if schedule == "overlap" else
                       f"total_ms={ms[schedule]:.2f}")
            emit(f"ring/range_count-{schedule}-r{r}",
                 ms[schedule] * 1e3 / NQ, derived)
            rows.append({"schedule": schedule, "r_shards": r,
                         "total_ms": ms[schedule],
                         "us_per_query": ms[schedule] * 1e3 / NQ,
                         "speedup_vs_serial": (speedup if schedule ==
                                               "overlap" else None)})
    save_json("ring_schedule", rows)
    return rows


if __name__ == "__main__":
    run()
