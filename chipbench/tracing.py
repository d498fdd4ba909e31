"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened to a list of events, each a dict with
`plane`, `line`, `name`, `start_ns` and `dur_ns`:

- every event of a device plane's op line and module line (the compiled
  programs, by module name);
- every host span the benchmark recorded (`jax.profiler.TraceAnnotation`
  names that start with `SPAN_PREFIX`).

`reduce_events` then takes the traced window from the benchmark's own
`WINDOW_SPAN` and works out, on the profiler's one clock:

- busy seconds: the union of the intervals in which an op ran, per
  device, averaged over the devices;
- device seconds and call count per compiled module;
- device seconds per op, named `<module>/<HLO instruction>`;
- the idle gaps, each named by the benchmark's host span that overlaps
  it most (the span the host was in while the device waited).

`tests/data/trace_small.json` is a recorded trace in the flattened form,
against which `tests/test_tracing.py` checks this reduction.
"""
from __future__ import annotations

import bisect
import collections

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "no_bench_span"


def flatten_xspace(path: str) -> list[dict]:
    """The events this module reads, from an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name,
                            "line": line.name if device else "host",
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns)})
    return out


def module_base(name: str) -> str:
    """A module event's program name without the trailing `(id)`."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """An op event's HLO instruction name (`%fusion.3`), without the
    instruction text the trace gives after it."""
    return name.split(" = ", 1)[0]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _gaps(busy, lo: int, hi: int) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _enclosing(modules: list, t: float) -> str:
    """The module (sorted (start, end, name) list) running at time t."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    return modules[i][2] if i >= 0 and modules[i][1] > t else "?"


def _name_gap(gap, spans, starts, longest) -> str:
    """The host span overlapping `gap` most; the shorter one on a tie.
    `spans` is sorted by start; `starts` and `longest` (its starts and
    its longest duration) let the search look only near the gap."""
    best, key = NO_SPAN, (0, 0)
    i = bisect.bisect_left(starts, gap[1])
    while i > 0 and starts[i - 1] > gap[0] - longest:
        i -= 1
        name, s, e = spans[i]
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = name, (ov, -(e - s))
    return best


def reduce_events(events: list[dict], top: int = 10) -> dict:
    """The numbers of one traced window (see the module docstring).
    Returns None where the trace holds no window span or no device op."""
    windows = [e for e in events if e["name"] == WINDOW_SPAN]
    if not windows:
        return None
    lo = min(e["start_ns"] for e in windows)
    hi = max(e["start_ns"] + e["dur_ns"] for e in windows)
    ops = collections.defaultdict(list)
    module_s = collections.Counter()
    module_calls = collections.Counter()
    op_s = collections.Counter()
    modules = collections.defaultdict(list)
    for e in events:
        if e["line"] == MODULES_LINE:
            modules[e["plane"]].append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"],
                 module_base(e["name"])))
    for m in modules.values():
        m.sort()
    for e in events:
        s, t = e["start_ns"], e["start_ns"] + e["dur_ns"]
        if e["line"] == OPS_LINE:
            c = _clip([(s, t)], lo, hi)
            if c:
                ops[e["plane"]].append(c[0])
                owner = _enclosing(modules[e["plane"]], s)
                op_s[f"{owner}/{op_name(e['name'])}"] += \
                    (c[0][1] - c[0][0]) * 1e-9
        elif e["line"] == MODULES_LINE and lo <= s < hi:
            module_s[module_base(e["name"])] += e["dur_ns"] * 1e-9
            module_calls[module_base(e["name"])] += 1
    if not ops:
        return None
    busy = {p: union(iv) for p, iv in ops.items()}
    busy_s = sum(sum(e - s for s, e in b) for b in busy.values()) \
        / len(busy) * 1e-9
    spans = sorted(((e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in events if e["line"] == "host"
                    and e["name"] != WINDOW_SPAN), key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    longest = max((e - s for _, s, e in spans), default=0)
    first = busy[sorted(busy)[0]]
    idle = collections.Counter()
    for g in _gaps(first, lo, hi):
        idle[_name_gap(g, spans, starts, longest)] += (g[1] - g[0]) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy_s,
        "devices": len(busy), "module_s": dict(module_s),
        "module_calls": dict(module_calls),
        "device_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[n, s] for n, s in idle.most_common(top)],
    }
