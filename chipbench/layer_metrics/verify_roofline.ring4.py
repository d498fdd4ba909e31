"""Share of one chip's verify time that the chip's peaks say its part
of the work needs, in %, where R is row-sharded over `chips` chips.

Each chip sweeps every searched query against its own shard of R, so it
needs 2 * n_searched * |R| * d / chips operations, and reads its shard
and the whole gathered query block once, in float32:
(|R| / chips + n_searched) * d * 4 bytes.  The least time for a call is
the larger of operations over the bf16 peak and bytes over the HBM
peak; the share is the traced calls' least time over one chip's verify
module time (the `jit_prog` seconds summed over the trace's device
planes, over `devices`).  The all-gather and the all-reduce count as
time, not as work, so time the chips spend exchanging shows as a lower
share."""
import sys

from peaks import peaks_for

#: the verify program's module name in the trace (see verify_device_ms.py)
MODULES = ("jit_prog",)


def work(n_searched: int, n_r: int, dim: int, chips: int) -> tuple:
    """(operations, bytes) one chip's verify needs for one call."""
    return (2 * n_searched * n_r * dim / chips,
            (n_r / chips + n_searched) * dim * 4)


def read(run):
    calls = [c for c in run.traced_calls() if c.n_searched > 0]
    if run.trace is None or not calls:
        return None
    device_s = sum(run.trace["module_s"].get(m, 0.0)
                   for m in MODULES) / run.trace["devices"]
    if device_s <= 0:
        return None
    peaks = peaks_for(run.device_kind)
    cfg, chips = run.cell.config, run.cell.chips
    least, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for c in calls:
        ops, nbytes = work(c.n_searched, int(cfg["n_r"]), int(cfg["dim"]),
                           chips)
        t_ops, t_mem = ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"]
        least += max(t_ops, t_mem)
        by["compute" if t_ops >= t_mem else "memory"] += 1
    print(f"verify_roofline.ring4: bound by {by} over {len(calls)} calls "
          f"on {chips} chips", file=sys.stderr)
    return 100.0 * least / device_s
