"""One run of one benchmark cell: set-up, the measured window, the check
against the plain reference, and the metrics.

Everything that belongs to one configuration, traffic mix or metric is
data or a reader found by name:

- `BENCHMARK.json`'s configuration entry names the file of sizes, and the
  file names its plain reference (`references/<reference>.py`);
- `traffic/<traffic>.json` holds the mix's parameters; its `route` names
  `routes/<route>.py` (how the plan is built, and what the route's
  guarantee says of an answer) and its `loop` names `loops/<loop>.py`
  (the warm-up and the measured closed loop);
- `checks/<workload>.json` holds the limits of the cell's check;
- `end_to_end/<metric>.py` and `layer_metrics/<metric>.py` each hold a
  `read(run)` that returns the metric from a `RunRecord`, or None where
  the run has nothing to read.

The program under test is imported only inside the functions that drive
it, after `run.py` has checked the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from typing import Optional
from unittest import mock

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: the profiler covers this much of the window's start in a `--trace 1` run
TRACE_SECONDS = 2.0


def log(msg: str) -> None:
    """One line on standard error."""
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    """Import the file at `path` as a module named `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ================================================================== the cell
@dataclasses.dataclass
class Cell:
    """A workload of `BENCHMARK.json` with its files resolved."""
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: list        # metric entries the cell reports with --trace 0
    per_layer: list         # metric entries the cell reports with --trace 1


def metric_reader(kind: str, name: str):
    """The `read(run)` of metric `name`; `kind` is "end_to_end" or
    "layer_metrics", the directory that holds it.  A metric named
    `<base>.<part>` (one quantity split by the cells that report it) is
    read by `<base>.py` unless it has a file of its own."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, kind, f"{name.split('.')[0]}.py")
    return load_module(path, f"chipbench_{kind}_{name}").read


def route_module(name: str):
    """`routes/<name>.py`: `build`, `wrong_mask`, `searched_mask`."""
    return load_module(os.path.join(BENCH_DIR, "routes", f"{name}.py"),
                       f"chipbench_route_{name}")


def loop_module(name: str):
    """`loops/<name>.py`: `warm_up` and `window`."""
    return load_module(os.path.join(BENCH_DIR, "loops", f"{name}.py"),
                       f"chipbench_loop_{name}")


def resolve_cell(bench: dict, workload: str, root: str = ROOT) -> Cell:
    """The cell `workload` of the benchmark `bench`, read from `root`."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"BENCHMARK.json has {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH_DIR, "checks", f"{workload}.json")) as f:
        checks = json.load(f)

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads",
                                                        [workload])]
    return Cell(workload, int(w["chips"]), config, traffic, checks,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def derived_seed(seed: int, salt: int) -> int:
    """A 31-bit seed for the program's own generators, drawn from the
    run's seed (which may be wider than 32 bits)."""
    return int(np.random.SeedSequence([seed, salt]).generate_state(1)[0]
               & 0x7FFFFFFF)


# ============================================================ the run record
@dataclasses.dataclass
class Call:
    """One call into the program in the window: its query rows are the
    flat pool's rows `start .. start + n` (wrapping), its answer
    `counts`."""
    start: int
    n: int
    t0: float
    t1: float = float("nan")
    n_searched: int = 0
    counts: Optional[np.ndarray] = None
    traced: bool = False


@dataclasses.dataclass
class RunRecord:
    """What a run measured, handed to every metric reader."""
    cell: Cell
    calls: list
    window_s: float
    setup_s: float
    compile_s: float
    spans: dict                 # set-up span name -> seconds
    peak_bytes: Optional[int]
    device_kind: str
    expected: Optional[np.ndarray] = None   # reference counts, flat pool
    trace: Optional[dict] = None            # `tracing.reduce_events`

    def expected_for(self, call: Call) -> np.ndarray:
        """The reference's counts for `call`'s rows."""
        n = len(self.expected)
        return self.expected[(call.start + np.arange(call.n)) % n]

    def traced_calls(self) -> list:
        """The calls whose whole span lies in the traced window."""
        return [c for c in self.calls if c.traced]


# ================================================================ clocks
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many
    programs it traced, from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        self.traces = 0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.total += secs
        if event == self.EVENTS[0]:
            self.traces += 1
        elif event == self.EVENTS[2]:
            self.backend_compiles += 1


class GcClock:
    """Collections the garbage collector ran, and their seconds, per
    generation, while `on` (a diagnostic of host stalls in the window)."""

    def __init__(self):
        self.on = False
        self.runs = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.runs[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def peak_device_bytes() -> Optional[int]:
    """Largest `peak_bytes_in_use` over the local devices since the
    process started (None where the backend reports no memory stats)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else max(peaks)


@contextlib.contextmanager
def timed_calls(spans: dict, targets: dict):
    """While open, add the seconds of every call of the functions in
    `targets` (span name -> (owner, attribute)) to `spans[name]`."""
    with contextlib.ExitStack() as stack:
        for name, (owner, attr) in targets.items():
            fn = getattr(owner, attr)

            @functools.wraps(fn)
            def timed(*a, _fn=fn, _name=name, **kw):
                import jax
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench.{_name}"):
                    out = _fn(*a, **kw)
                spans[_name] = spans.get(_name, 0.0) + time.perf_counter() - t0
                return out

            stack.enter_context(mock.patch.object(owner, attr, timed))
        yield


# ================================================================== set-up
class Pool:
    """The cell's query rows `Q`: sets of `rows` rows, cycled; a call's
    rows are addressed in `Q`, wrapping at its end."""

    def __init__(self, Q: np.ndarray, rows: int):
        self.Q, self.rows, self.n = Q, rows, len(Q)

    def rows_at(self, start: int, n: int) -> np.ndarray:
        s = start % self.n
        if s + n <= self.n:
            return self.Q[s:s + n]
        return self.Q[(s + np.arange(n)) % self.n]


# ================================================================== window
class Tracer:
    """The profiler over the first `seconds` of the window (a no-op when
    `on` is false), with the benchmark's window span inside it."""

    def __init__(self, on: bool, seconds: float):
        self.on, self.seconds = on, seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_") if on else None
        self.active = False
        self._span = None

    def start(self) -> None:
        if self.on:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # Python calls: cost, no use
            opts.host_tracer_level = 1      # the benchmark's own spans
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.t0 = time.perf_counter()
            self.active = True

    def maybe_stop(self, now: float, force: bool = False) -> bool:
        """Stop once `seconds` have passed; True while still tracing."""
        if self.active and (force or now - self.t0 >= self.seconds):
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
        return self.active

    def events(self) -> Optional[list]:
        """The flattened trace (None when tracing was off)."""
        if not self.on:
            return None
        from tracing import flatten_xspace
        paths = [os.path.join(d, f) for d, _, fs in os.walk(self.dir)
                 for f in fs if f.endswith(".xplane.pb")]
        return flatten_xspace(paths[0]) if paths else []

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


# =================================================================== check
def wrong_mask(route, got, exp: np.ndarray) -> np.ndarray:
    """bool [n]: the answers of one call that are wrong or never came,
    by the guarantee of `route` (a module of `routes/`)."""
    if got is None or np.shape(got) != exp.shape:
        return np.ones(exp.shape, bool)
    return route.wrong_mask(np.asarray(got), exp)


def check_counts(route, calls: list, record: RunRecord) -> dict:
    """Numbers the check compares, neither growing with the window:

    - `wrong_queries`: the pool's queries that got a wrong or missing
      answer in any call of the window (a query asked again and answered
      alike counts once);
    - `missed_pair_frac`: true pairs the window's answers did not find,
      over all true pairs of its calls (1 - recall; an answer that never
      came finds none)."""
    wrong = set()
    found = true = 0
    for c in calls:
        exp = record.expected_for(c)
        bad = wrong_mask(route, c.counts, exp)
        wrong.update(((c.start + np.flatnonzero(bad)) % len(record.expected))
                     .tolist())
        true += int(exp.sum())
        if c.counts is not None and np.shape(c.counts) == exp.shape:
            found += int(np.minimum(c.counts, exp).sum())
    return {"wrong_queries": len(wrong),
            "missed_pair_frac": 1.0 - found / true if true else 0.0}


def missed_frac(got, exp: np.ndarray) -> float:
    """True pairs of one call that its answers did not find, over its
    true pairs."""
    true = int(exp.sum())
    if not true:
        return 0.0
    if got is None or np.shape(got) != exp.shape:
        return 1.0
    return 1.0 - int(np.minimum(got, exp).sum()) / true


def failed_calls(route, calls: list, record: RunRecord, checks: dict,
                 limits: dict) -> int:
    """Calls with a wrong or missing answer; and, where the window missed
    more true pairs than `limits["missed_pair_frac"]` allows, the calls
    whose own answers miss more than that."""
    max_missed = limits.get("missed_pair_frac", float("inf"))
    if checks.get("missed_pair_frac", 0.0) <= max_missed:
        max_missed = float("inf")
    return sum(1 for c in calls
               if wrong_mask(route, c.counts, record.expected_for(c)).any()
               or missed_frac(c.counts, record.expected_for(c)) > max_missed)


def reference_counts(config: dict, pool: Pool, R: np.ndarray,
                     precision: str = "highest") -> np.ndarray:
    """The configuration's plain reference over the flat pool."""
    ref = load_module(os.path.join(BENCH_DIR, "references",
                                   f"{config['reference']}.py"),
                      f"chipbench_reference_{config['reference']}")
    return ref.counts(pool.Q, R, float(config["eps"]), config["metric"],
                      precision=precision)


# ===================================================================== run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: Optional[float] = None, device_kind: str = "",
             plan_hook=None) -> dict:
    """Set up, measure, check; returns the result line as a dict.
    `plan_hook(plan)`, for tests, may replace the built plan's timed
    path before the window."""
    import jax
    from corpus import draw
    import tracing

    t_process = time.perf_counter() if t_process is None else t_process
    cfg, traffic = cell.config, cell.traffic
    route, loop = route_module(traffic["route"]), loop_module(traffic["loop"])
    clock = CompileClock()
    spans: dict = {}
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.draw"):
        R, Q = draw(cfg, seed, int(traffic["pool"]), int(traffic["rows"]))
    log(f"draw: {len(R) + len(Q)} rows x {R.shape[1]}, "
        f"{R.nbytes + Q.nbytes} bytes in {time.perf_counter() - t0:.3f} s")
    pool = Pool(Q, int(traffic["rows"]))
    eps = float(cfg["eps"])
    plan = route.build(cfg, R, seed, spans)
    if plan_hook is not None:
        plan = plan_hook(plan)
    loop.warm_up(plan, pool, traffic, eps)
    setup_compile_s = clock.total
    traces0, compiles0 = clock.traces, clock.backend_compiles
    tracer = Tracer(trace, min(TRACE_SECONDS, seconds))
    setup_s = time.perf_counter() - t_process
    gc_clock = GcClock()
    try:
        tracer.start()
        gc_clock.on = True
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        t_window = time.perf_counter()
        calls = loop.window(plan, pool, traffic, eps, seconds, tracer)
        window_s = time.perf_counter() - t_window
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        gc_clock.on = False
        tracer.maybe_stop(time.perf_counter(), force=True)
        peak = peak_device_bytes()
        log(f"window: {len(calls)} calls in {window_s:.3f} s; programs "
            f"traced in the window {clock.traces - traces0}, compiled "
            f"{clock.backend_compiles - compiles0}")
        lat = np.array([c.t1 - c.t0 for c in calls]) * 1e3
        searched = np.array([c.n_searched for c in calls])
        slow = lat > 1.25 * np.median(lat)
        log(f"window calls: latency ms p50 {np.median(lat):.3f} p90 "
            f"{np.percentile(lat, 90):.3f} max {lat.max():.3f}; over 1.25 x "
            f"p50: {int(slow.sum())} calls, "
            f"{(lat[slow] - np.median(lat)).sum() / 1e3:.3f} s beyond p50; "
            f"n_searched min {searched.min()} p50 {np.median(searched):.0f} "
            f"max {searched.max()}")
        log(f"window host: gc collections by generation {gc_clock.runs}, "
            f"seconds {[round(t, 4) for t in gc_clock.seconds]}; minor page "
            f"faults {ru1.ru_minflt - ru0.ru_minflt}, context switches "
            f"voluntary {ru1.ru_nvcsw - ru0.ru_nvcsw} involuntary "
            f"{ru1.ru_nivcsw - ru0.ru_nivcsw}")
        events = tracer.events()
    finally:
        gc_clock.close()
        tracer.close()
    del plan
    from repro.core.engine import clear_program_cache
    clear_program_cache()
    jax.clear_caches()
    gc.collect()

    t0 = time.perf_counter()
    record = RunRecord(cell=cell, calls=calls, window_s=window_s,
                       setup_s=setup_s, compile_s=setup_compile_s,
                       spans=spans, peak_bytes=peak, device_kind=device_kind)
    record.expected = reference_counts(cfg, pool, R)
    log(f"reference: {pool.n} queries in {time.perf_counter() - t0:.3f} s")
    record.trace = tracing.reduce_events(events) if events is not None \
        else None

    checks = check_counts(route, calls, record)
    limits = {k: v["limit"] for k, v in cell.checks.items()}
    correct = all(checks[k] <= limits[k] for k in limits)
    metrics = {}
    kind, entries = (("layer_metrics", cell.per_layer) if trace
                     else ("end_to_end", cell.end_to_end))
    for m in entries:
        value = metric_reader(kind, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(calls),
           "failed": failed_calls(route, calls, record, checks, limits),
           "metrics": metrics}
    if trace and record.trace is not None:
        out["breakdown"] = {"device_ops": record.trace["device_ops"],
                            "idle_gaps": record.trace["idle_gaps"]}
        out["_trace"] = record.trace
    out["_peak_bytes"] = peak
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in limits}
    return out
