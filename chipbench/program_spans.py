"""The program's own host spans in a profiler trace, and the device's
idle time split by them.

The join program opens a host span named `join.<step>` at each stage
boundary of its pipeline (`repro.core.engine._span`), with integer counts
as the span's arguments: `join.run` / `join.submit` / `join.flush` (the
plan's calls), `join.stage` (`batch`, `rows`, `h2d_bytes`) around
`join.stage.pad`, `join.stage.upload` and `join.stage.filter`,
`join.probe` (`batch`), `join.verify` (`batch`, `n_pos`, `capacity`),
and `join.sync.<kind>` (`batch`) around every declared device-to-host
wait.

`flatten_program` reads them from an `.xplane.pb` file in the flattened
form of `tracing.py`, with `line: "program"` and the counts under
`args`.  `tracing.reduce_events` reads no event of that line, so the
events can ride in the same list.  `reduce_program` takes the traced
window and the first device's idle intervals as `tracing.reduce_events`
computes them (its `lo`, `hi` and `_gaps(first, lo, hi)`), and returns:

- `idle_s`: per innermost `join.` span name, the idle seconds of the
  first device while that span was the innermost one open on the host;
  `OUTSIDE` holds the idle time no `join.` span covers (the caller's own
  code between calls);
- `spans`: per span name, `[count, self seconds]` over the window (a span
  counts where it starts; its self time is where it is innermost);
- `counts`: per span name that carries counts, their sums over the
  window's spans (`batch`, an identifier, is not summed).

`idle_frac` groups `idle_s` into the four parts the per-layer metrics
read: `stage` (`join.stage*`), `sync` (`join.sync.*`), `dispatch` (any
other `join.` span) and `outside`.  On one chip the four sum to
`device_idle_frac`.
"""
from __future__ import annotations

import collections

PROGRAM_PREFIX = "join."
PROGRAM_LINE = "program"
OUTSIDE = "outside"
ID_ARGS = ("batch",)


def flatten_program(path: str) -> list[dict]:
    """The program's `join.` host spans of an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append({"plane": plane.name, "line": PROGRAM_LINE,
                                "name": ev.name,
                                "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns),
                                "args": {k: v for k, v in ev.stats}})
    return out


def group(name: str) -> str:
    """The part of the idle split a span name belongs to."""
    if name == OUTSIDE:
        return "outside"
    if name == "join.stage" or name.startswith("join.stage."):
        return "stage"
    if name.startswith("join.sync."):
        return "sync"
    return "dispatch"


def _innermost_pieces(spans: list, lo: int, hi: int) -> list:
    """[lo, hi) cut into (start, end, name) pieces, `name` the innermost
    span open over the piece (the latest started; `OUTSIDE` where none
    is open).  `spans` is a list of (name, start, end)."""
    marks = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            marks += [(s, 1, i), (e, 0, i)]
    marks.sort()                    # at one time, ends before starts
    active, t, out = set(), lo, []

    def innermost():
        if not active:
            return OUTSIDE
        i = max(active, key=lambda j: (spans[j][1], -spans[j][2]))
        return spans[i][0]
    for when, starts, i in marks:
        if when > t:
            out.append((t, when, innermost()))
            t = when
        if starts:
            active.add(i)
        else:
            active.discard(i)
    if hi > t:
        out.append((t, hi, OUTSIDE))
    return out


def _overlap(pieces: list, gaps: list):
    """(name, ns) for each overlap of sorted, disjoint `pieces` and
    `gaps`."""
    i = j = 0
    while i < len(pieces) and j < len(gaps):
        a = max(pieces[i][0], gaps[j][0])
        b = min(pieces[i][1], gaps[j][1])
        if b > a:
            yield pieces[i][2], b - a
        if pieces[i][1] <= gaps[j][1]:
            i += 1
        else:
            j += 1


def reduce_program(events: list[dict], lo: int, hi: int,
                   gaps: list) -> dict:
    """The program's part of the traced window [lo, hi) whose first
    device idles over the sorted, disjoint intervals `gaps` (see the
    module docstring)."""
    prog = [e for e in events if e["line"] == PROGRAM_LINE]
    spans = [(e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"])
             for e in prog]
    pieces = _innermost_pieces(spans, lo, hi)
    idle = collections.Counter()
    for name, ns in _overlap(pieces, gaps):
        idle[name] += ns * 1e-9
    self_s = collections.Counter()
    for s, e, name in pieces:
        if name != OUTSIDE:
            self_s[name] += (e - s) * 1e-9
    n = collections.Counter()
    sums = collections.defaultdict(collections.Counter)
    for e in prog:
        if lo <= e["start_ns"] < hi:
            n[e["name"]] += 1
            sums[e["name"]].update({k: v for k, v in e.get("args", {}).items()
                                    if k not in ID_ARGS})
    return {"idle_s": dict(idle),
            "spans": {k: [n[k], self_s[k]] for k in sorted(n)},
            "counts": {k: dict(v) for k, v in sorted(sums.items()) if v}}


def _program(trace: dict | None) -> dict | None:
    p = (trace or {}).get("program")
    return p if p and p["spans"] else None


def idle_frac(trace: dict | None, part: str) -> float | None:
    """The window's share in which the device idled under `part` of the
    split (`stage`, `sync`, `dispatch` or `outside`); None where the trace
    holds no program span."""
    p = _program(trace)
    if p is None or trace["window_s"] <= 0:
        return None
    return sum(s for name, s in p["idle_s"].items()
               if group(name) == part) / trace["window_s"]


def count_sums(trace: dict | None, span: str) -> dict | None:
    """The sums of `span`'s counts over the window; None where the window
    holds no such span."""
    p = _program(trace)
    return None if p is None else p["counts"].get(span)
