"""Share of the verify program's device time that the chip's peaks say
the work needs, in %.

The work is what the algorithm needs for the rows actually searched,
whatever implements verify: 2 * n_searched * |R| * d operations, and
|R| * d * 4 + n_searched * d * 4 bytes (R and the searched queries, in
float32, read once).  The least time for a call is the larger of
operations over the bf16 peak and bytes over the HBM peak; the share is
the traced calls' least time over the verify module's device time.
Padding rows, and passes beyond one over bf16, show as a lower share."""
import sys

from peaks import peaks_for

#: the verify program's module name in the trace (see verify_device_ms.py)
MODULES = ("jit_prog",)


def work(n_searched: int, n_r: int, dim: int) -> tuple:
    """(operations, bytes) verify needs for one call."""
    return 2 * n_searched * n_r * dim, (n_r + n_searched) * dim * 4


def read(run):
    calls = [c for c in run.traced_calls() if c.n_searched > 0]
    if run.trace is None or not calls:
        return None
    device_s = sum(run.trace["module_s"].get(m, 0.0)
                   for m in MODULES)
    if device_s <= 0:
        return None
    peaks = peaks_for(run.device_kind)
    cfg = run.cell.config
    least, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for c in calls:
        ops, nbytes = work(c.n_searched, int(cfg["n_r"]), int(cfg["dim"]))
        t_ops, t_mem = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
        least += max(t_ops, t_mem)
        by["compute" if t_ops >= t_mem else "memory"] += 1
    print(f"verify_roofline: bound by {by} over {len(calls)} calls",
          file=sys.stderr)
    return 100.0 * least / device_s
