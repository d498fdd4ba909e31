"""Protocol-first public join API: `JoinPlan` + the Filter/Searcher
contracts (DESIGN.md §9).

The paper's headline claim is that Xling "acts as a flexible plugin that
can be inserted to any loop-based similarity join method" (§IV-C). This
module is the contract that makes the claim structural rather than
special-cased:

  * `Filter` — anything that can veto queries: `verdicts(Q, eps)` is the
    host form; an optional `device_filter(eps) -> (predict, threshold)`
    is the fused form the engine compiles into its filter program.
    Adapters (`as_filter`) lift `XlingFilter`, the `LSBF` baseline, and
    bare callables onto the protocol, replacing the old isinstance
    dispatch in `xjoin.py`.
  * `Searcher` — anything that can find neighbors: `query_counts(Q, eps)`
    is the whole-join form; `candidates(Q[, eps])` is the probing half of
    the host-probe / device-verify split (`joins/common.py`). Every
    registered join method implements the protocol, so ANY base — not
    just the naive sweep — routes its predicted-positive queries through
    `JoinEngine`'s device-resident candidate verification and the
    asynchronous streaming pipeline.
  * `JoinPlan` — the single declarative entry point tying both together:

        plan = (JoinPlan(R, "cosine")
                .filter("xling", tau=50, xdt="fpr")
                .search("lsh", k=14, l=10)
                .on(mesh=mesh, backend="auto"))
        res = plan.run(Q, eps=0.45)
        for r in plan.stream(batches, eps=0.45, depth=2): ...

    The whole configuration is validated once at `build()` (invalid
    filter/search/verify combinations fail there with an actionable
    message, not data-dependently mid-stream), the engine and device
    programs are constructed once and cached across calls, and
    `describe()` returns a serializable summary of the plan (used by the
    serve CLI and the benchmarks).

`FilteredJoin` / `build_xjoin` / `enhance_with_xling` (core/xjoin.py)
remain as thin legacy shims over `JoinPlan`.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import (Any, Callable, Iterable, Iterator, Optional, Protocol,
                    runtime_checkable)

import numpy as np

from repro.core.engine import VERIFY_BACKENDS, JoinEngine, _span
from repro.core.topology import resolve_topology
from repro.core.joins import JOINS, make_join
from repro.core.joins.lsbf import LSBF
from repro.core.joins.naive import NaiveJoin
from repro.core.xling import XlingConfig, XlingFilter


# =========================================================== the protocols
@runtime_checkable
class Filter(Protocol):
    """A query veto: predicts which queries are worth searching.

    Required: `verdicts(Q, eps) -> bool [q]` (host form). Optional:
    `device_filter(eps) -> (predict, threshold) | None` — the fused form;
    `predict` is an estimator's `(params, fn)` pair and `threshold` the
    calibrated XDT cut, compiled by the engine into one device program."""

    def verdicts(self, Q: np.ndarray, eps: float) -> np.ndarray:
        """bool [q]: True = search this query, False = skip it."""
        ...


@runtime_checkable
class Searcher(Protocol):
    """A join method over a fixed index set R.

    Required: `query_counts(Q, eps) -> int32 [q]` plus `name` / `exact`
    attributes. Optional (the probe/verify split): `candidates(Q[, eps])
    -> int32 [q, C]` (-1 padded) — when present, the engine verifies the
    candidates on device against its resident R; `eps` is passed only to
    eps-aware probes (see `joins.common.searcher_candidates`)."""

    def query_counts(self, Q: np.ndarray, eps: float) -> np.ndarray:
        """int32 [q] found-neighbor counts per query."""
        ...


@runtime_checkable
class DeviceSearcher(Searcher, Protocol):
    """A Searcher whose index probe can run ON the mesh (DESIGN.md §11).

    `device_probe(eps)` is the Searcher analogue of
    `Filter.device_filter`: it returns a probe spec (`core/probe.py` —
    an object exposing `place(engine) -> PlacedProbe`) or None when the
    index cannot probe on device. The engine places each distinct spec
    once (tables uploaded and pinned like R, per the topology) and then
    runs probe -> candidate verification entirely on device, leaving the
    positive-count read as the only per-batch host sync. Contract:
    `eps` may be None (plan-build/validation calls) — return the
    radius-free spec or None; radius-DEPENDENT probes must return one
    (preferably memoized) spec per distinct eps, since placement is
    cached by spec identity. Searchers whose classes cannot grow the
    method register a builder in `probe.PROBE_BUILDERS` instead;
    searchers doing neither simply keep the host probe path."""

    def device_probe(self, eps: float):
        """Probe spec for the engine to place on its mesh, or None."""
        ...


# ======================================================== filter adapters
class XlingAdapter:
    """`XlingFilter` on the Filter protocol: verdicts via the estimator +
    XDT threshold; the fused device form when the estimator exposes
    `device_predict_fn` (all registry estimators do)."""

    def __init__(self, filt: XlingFilter, *, tau: int = 0,
                 xdt_mode: Optional[str] = None,
                 fpr_tolerance: Optional[float] = None):
        self.filt = filt
        self.tau = int(tau)
        self.xdt_mode = xdt_mode
        self.fpr_tolerance = fpr_tolerance

    def verdicts(self, Q: np.ndarray, eps: float) -> np.ndarray:
        """Host-side verdicts: predicted count vs the XDT threshold."""
        pos, _ = self.filt.query(Q, eps, self.tau, mode=self.xdt_mode,
                                 fpr_tolerance=self.fpr_tolerance)
        return pos

    def device_filter(self, eps: float):
        """(predict, threshold) for the engine's fused filter program; the
        XDT threshold is calibrated through the same device fn that will
        produce the online predictions (float parity at the boundary)."""
        est = self.filt.estimator
        if not hasattr(est, "device_predict_fn"):
            return None
        predict = est.device_predict_fn()
        threshold = self.filt.xdt(eps, self.tau, mode=self.xdt_mode,
                                  fpr_tolerance=self.fpr_tolerance,
                                  predict=predict)
        return predict, threshold


class LSBFAdapter:
    """`LSBF` (the MSBF baseline) on the Filter protocol. Its verdict is
    radius-blind (bit-array membership), so `eps` is ignored; there is no
    device form — verdicts are computed on host per batch."""

    def __init__(self, filt: LSBF):
        self.filt = filt
        self.tau = 0                        # LSBF answers "any neighbor"

    def verdicts(self, Q: np.ndarray, eps: float) -> np.ndarray:
        """Host-side verdicts from the locality-sensitive bit array."""
        return self.filt.query(Q)


class CallableAdapter:
    """A bare `fn(Q, eps) -> bool [q]` on the Filter protocol (host-only;
    the escape hatch for experiment-specific filters)."""

    def __init__(self, fn: Callable[[np.ndarray, float], np.ndarray]):
        self.fn = fn
        self.tau = 0

    def verdicts(self, Q: np.ndarray, eps: float) -> np.ndarray:
        """Host-side verdicts from the wrapped callable."""
        return np.asarray(self.fn(Q, eps), bool)


#: Adapter registry: concrete filter type -> adapter factory. `as_filter`
#: walks an object's MRO through this table, so new filter types plug in
#: by registration instead of editing an isinstance chain.
FILTER_ADAPTERS: dict[type, Callable[..., Any]] = {
    XlingFilter: XlingAdapter,
    LSBF: lambda f, **_: LSBFAdapter(f),
}


def as_filter(obj, *, tau: int = 0, xdt_mode: Optional[str] = None,
              fpr_tolerance: Optional[float] = None):
    """Coerce `obj` onto the Filter protocol (None passes through).

    Resolution order: objects already exposing `verdicts` are returned
    as-is; registered concrete types (`FILTER_ADAPTERS`) are wrapped with
    their adapter (Xling adapters receive the tau/XDT knobs); any other
    callable is wrapped as `fn(Q, eps) -> bool [q]`. Raises TypeError for
    everything else, and ValueError when tau/XDT knobs are given for a
    filter that cannot honor them (LSBF, callables, prebuilt protocol
    objects) — silently dropping a declared tau would change semantics."""
    def _reject_knobs(kind: str):
        if tau or xdt_mode is not None or fpr_tolerance is not None:
            raise ValueError(
                f"filter options tau/xdt/fpr_tolerance do not apply to "
                f"{kind}: they parameterize the Xling XDT decision; "
                "configure the object itself instead")

    if obj is None:
        return None
    if isinstance(obj, Filter):             # protocol: has verdicts()
        # a prebuilt adapter carries its own knobs — new ones cannot be
        # grafted on (an XlingAdapter's threshold caches would go stale),
        # so they are rejected rather than silently dropped
        _reject_knobs(f"a prebuilt Filter object ({type(obj).__name__}); "
                      "pass the raw XlingFilter to apply them")
        return obj
    for cls in type(obj).__mro__:
        adapt = FILTER_ADAPTERS.get(cls)
        if adapt is not None:
            if adapt is not XlingAdapter:
                _reject_knobs(type(obj).__name__)
            return adapt(obj, tau=tau, xdt_mode=xdt_mode,
                         fpr_tolerance=fpr_tolerance)
    if callable(obj):
        _reject_knobs("a callable filter")
        return CallableAdapter(obj)
    raise TypeError(
        f"unsupported filter {type(obj).__name__}: expected an object with "
        "verdicts(Q, eps), a registered filter type "
        f"({[c.__name__ for c in FILTER_ADAPTERS]}), or a callable "
        "fn(Q, eps) -> bool [q]")


def _filter_label(f) -> Optional[str]:
    """Human-readable filter name for describe()/meta (the wrapped concrete
    type where the adapter kept it, the adapter type otherwise)."""
    if f is None:
        return None
    for attr in ("filt", "fn"):
        inner = getattr(f, attr, None)
        if inner is not None:
            return type(inner).__name__
    return type(f).__name__


# ============================================================== the plan
@dataclass
class JoinResult:
    """Per-call join outcome: exact-at-candidates neighbor counts plus
    provenance metadata.  Where the time goes is in a profiler trace:
    the call's `join.` spans (DESIGN.md §12)."""
    counts: np.ndarray
    n_queries: int
    n_searched: int
    meta: dict = field(default_factory=dict)

    def recall_vs(self, true_counts: np.ndarray) -> float:
        """Pair-level recall: found pairs over true pairs (count-based —
        exact for exact searchers; an upper-bound-free measure for
        approximate searchers since found <= true per query)."""
        denom = float(np.sum(true_counts))
        if denom == 0:
            return 1.0
        return float(np.sum(np.minimum(self.counts, true_counts)) / denom)


@dataclass
class _BuiltPlan:
    """Resolved plan state: constructed engine/base/filter/verify route."""
    engine: JoinEngine
    base: Any
    filter: Optional[Any]
    verify_route: Any                       # "exact" | Searcher object
    verify_label: str
    placed_probe: Any = None                # PlacedProbe | None (§11)


def _spec_name(spec) -> str:
    """Display name of a filter/search/verify spec (string or instance)."""
    return spec if isinstance(spec, str) else type(spec).__name__


class JoinPlan:
    """Declarative, validated join configuration — the single entry point.

    Compose with the fluent builders (`filter` / `search` / `verify` /
    `on`), then `run`, `stream`, or inspect with `describe`. `build()` is
    called implicitly on first use; it validates the WHOLE configuration
    up front (unknown names, impossible filter/search/verify combinations,
    mismatched engines all fail there with actionable messages), fits the
    filter if it was given by name, pins R on device via a `JoinEngine`,
    and caches every compiled program across calls.

    Execution always flows through the engine (DESIGN.md §4–§5): the
    filter runs fused on device when it has a device form (host verdicts
    are uploaded otherwise), positives are compacted into bucketed static
    shapes, and verification is the engine's exact sweep (naive base),
    the verify searcher's `candidates()` checked on device against the
    engine's resident R, or — for candidate-less plug-ins — the
    searcher's own `query_counts()` over the compacted positives. That is
    how EVERY join method, not just the naive sweep, gets the
    fused-skipping and async-streaming machinery."""

    _ON_KEYS = ("mesh", "backend", "block", "engine", "cache_key",
                "topology", "r_shards", "probe", "plan")

    def __init__(self, R: np.ndarray, metric: str = "cosine"):
        self._R = np.asarray(R, np.float32)
        self.metric = str(metric)
        self._filter_spec: tuple[Any, dict] = (None, {})
        self._search_spec: tuple[Any, dict] = ("naive", {})
        self._verify_spec: tuple[Any, dict] = ("auto", {})
        self._exec: dict = {"mesh": None, "backend": "auto", "block": 512,
                            "engine": None, "cache_key": None,
                            "topology": None, "r_shards": None,
                            "probe": "auto", "plan": None}
        self._built: Optional[_BuiltPlan] = None
        self._device_filter_cache: dict = {}
        self._mutable = False
        self._auto_compact_at: Optional[float] = None
        self._seen_compactions = 0
        #: set by the auto-planner (core/planner.py, DESIGN.md §16): the
        #: machine-readable plan rationale on planner-produced plans, the
        #: chosen stream depth, and — on `on(plan="auto")` lazy plans —
        #: the planned delegate built at first run/session
        self._planner_explain: Optional[dict] = None
        self._planned_depth: Optional[int] = None
        self._auto_delegate: Optional["JoinPlan"] = None

    # ------------------------------------------------------------ builders
    def filter(self, filt="xling", **opts) -> "JoinPlan":
        """Select the filter: "xling" (fitted on R at build time; `tau`,
        `xdt`/`xdt_mode`, `fpr_tolerance` plus any `XlingConfig` field as
        keywords), "lsbf" (the MSBF baseline; LSBF constructor params),
        "none", a Filter-protocol object, a concrete `XlingFilter`/`LSBF`
        instance, or a callable `fn(Q, eps) -> bool [q]`."""
        self._filter_spec = (filt, dict(opts))
        self._built = None
        self._auto_delegate = None
        return self

    def search(self, method="naive", **params) -> "JoinPlan":
        """Select the base join method: a registry name (`JOINS` — naive,
        grid, lsh, kmeanstree, ivfpq) with constructor params, or a
        Searcher instance already built over this plan's R."""
        self._search_spec = (method, dict(params))
        self._built = None
        self._auto_delegate = None
        return self

    def verify(self, backend="auto", **params) -> "JoinPlan":
        """Select how predicted-positive queries are verified: "auto"
        (exact sweep for the naive base; otherwise the base verifies its
        own positives — device candidate verification when it exposes
        `candidates()`, its own `query_counts()` when not — the default),
        "exact" (engine brute-force sweep; naive base only), a join name
        (lsh/ivfpq with engine-cached indices — explicit params pin the
        built instance to this plan — or grid/kmeanstree), or a Searcher
        instance (candidates() or query_counts()).

        Naming a backend REPLACES the verification route entirely: with a
        non-naive base the base's own probe is then bypassed (only the
        filter gates which queries reach the named backend) —
        `describe()["search"]["active"]` reports whether the base
        actually participates."""
        self._verify_spec = (backend, dict(params))
        self._built = None
        self._auto_delegate = None
        return self

    def on(self, **opts) -> "JoinPlan":
        """Set execution placement: `mesh` (query-axis sharding via
        `launch.mesh.make_data_mesh` / `make_join_mesh`), `backend`
        (DESIGN.md §2 kernel matrix), `block` (compaction bucket
        quantum), `engine` (share a prebuilt `JoinEngine` over the same
        R), `cache_key` (ground-truth table disk cache for the xling
        fit), `topology` ("replicated" | "ring" | a `Topology` instance
        — where R lives on the mesh, DESIGN.md §10), `r_shards` (ring
        only: size of the R-sharding mesh axis; when no mesh is given the
        plan builds a `make_join_mesh(r=r_shards)` over the local
        devices), `probe` ("auto" | "device" | "host", DESIGN.md §11 —
        where the approximate verify route's index probe runs; "auto"
        picks the device whenever the searcher advertises
        `device_probe`, "device" requires it and fails at build when
        unavailable), `plan` (None | "auto" — "auto" defers to the
        cost-based planner, DESIGN.md §16: the first run/session
        measures the workload and delegates to the planner-chosen
        configuration; explicit knobs set here are respected as pinned
        constraints). `describe()["exec"]["topology"]` /
        `describe()["exec"]["probe"]` report the resolved placement
        including per-device R and probe-table bytes."""
        unknown = set(opts) - set(self._ON_KEYS)
        if unknown:
            raise ValueError(f"on(): unknown option(s) {sorted(unknown)}; "
                             f"expected {list(self._ON_KEYS)}")
        if opts.get("plan") not in (None, "auto"):
            raise ValueError(f"on(plan={opts['plan']!r}): expected None or "
                             "'auto' (the cost-based planner)")
        self._exec.update(opts)
        self._built = None
        self._auto_delegate = None
        return self

    def mutable(self, auto_compact_at: Optional[float] = 0.5) -> "JoinPlan":
        """Opt this plan into dynamic R (DESIGN.md §13): unlock
        `insert` / `delete` / `compact` on the plan and set the engine's
        auto-compaction policy — the delta is merged into the pinned R
        (and any verifier indices are rebuilt) once
        (|delta| + |tombstones|) / |R| reaches `auto_compact_at`; pass
        None to compact only on explicit `compact()` calls.

        Mutable plans require `search("naive")` and a by-name verify
        spec (`"auto"`, `"exact"`, `"lsh"`, `"ivfpq"`): instance
        searchers hold their own host-side copy of R that the engine
        cannot patch, so mutations would silently diverge — build()
        rejects the combination with an actionable error instead."""
        if auto_compact_at is not None and not auto_compact_at > 0.0:
            raise ValueError(
                f"mutable(auto_compact_at={auto_compact_at}): expected a "
                "positive delta fraction, or None to disable auto-compaction")
        self._mutable = True
        self._auto_compact_at = (None if auto_compact_at is None
                                 else float(auto_compact_at))
        self._built = None
        return self

    # ---------------------------------------------------------- validation
    def _same_R(self, other_R) -> bool:
        """Same-index-set check: identity fast path, else full equality —
        a host memcmp, cheap next to the device upload build() performs,
        and the only check that actually closes the wrong-R hazard (a
        corpus differing in interior rows would otherwise be verified
        against silently)."""
        other_R = np.asarray(other_R)
        if other_R is self._R:
            return True
        return (other_R.shape == self._R.shape
                and bool(np.array_equal(other_R, self._R)))

    def _build_base(self, engine: JoinEngine):
        spec, params = self._search_spec
        if isinstance(spec, str):
            if spec not in JOINS:
                raise ValueError(f"search({spec!r}): unknown join method; "
                                 f"registered: {sorted(JOINS)}")
            if spec == "naive":
                return make_join("naive", self._R, self.metric,
                                 backend=self._exec["backend"], engine=engine,
                                 **params)
            return make_join(spec, self._R, self.metric, **params)
        if not isinstance(spec, Searcher):
            raise ValueError(
                f"search({type(spec).__name__}): instance must satisfy the "
                "Searcher protocol (query_counts(Q, eps))")
        if getattr(spec, "metric", self.metric) != self.metric:
            raise ValueError(
                f"search({type(spec).__name__}): instance is built for "
                f"metric {getattr(spec, 'metric')!r}, the plan for "
                f"{self.metric!r} — its probe geometry would not match the "
                "verification distances")
        if not self._same_R(getattr(spec, "R", self._R)):
            raise ValueError(
                f"search({type(spec).__name__}): instance is indexed over a "
                "different R than this plan — rebuild it over the plan's R "
                "or pass that R to JoinPlan()")
        return spec

    def _build_filter(self, engine: JoinEngine):
        spec, opts = self._filter_spec
        if spec is None or spec == "none":
            return None
        opts = dict(opts)
        tau = int(opts.pop("tau", 0))
        xdt_mode = opts.pop("xdt", opts.pop("xdt_mode", None))
        fpr_tolerance = opts.pop("fpr_tolerance", None)
        if tau < 0:
            raise ValueError(f"filter(tau={tau}): tau must be >= 0")
        if xdt_mode not in (None, "fpr", "mean"):
            raise ValueError(f"filter(xdt={xdt_mode!r}): expected 'fpr' or "
                             "'mean'")
        if fpr_tolerance is not None and not 0.0 < fpr_tolerance < 1.0:
            raise ValueError(f"filter(fpr_tolerance={fpr_tolerance}): "
                             "expected a rate in (0, 1)")
        if isinstance(spec, str):
            if spec == "xling":
                cfg = XlingConfig(metric=self.metric,
                                  xdt_mode=xdt_mode or "fpr",
                                  fpr_tolerance=(0.05 if fpr_tolerance is None
                                                 else fpr_tolerance),
                                  backend=self._exec["backend"], **opts)
                # the plan's engine already holds R device-resident —
                # the ground-truth fit sweep reuses it instead of
                # re-uploading (groundtruth.cardinality_table engine=)
                filt = XlingFilter(cfg).fit(
                    self._R, cache_key=self._exec["cache_key"],
                    mesh=self._exec["mesh"], engine=engine)
                return XlingAdapter(filt, tau=tau, xdt_mode=xdt_mode,
                                    fpr_tolerance=fpr_tolerance)
            if spec == "lsbf":
                if tau or xdt_mode is not None or fpr_tolerance is not None:
                    raise ValueError(
                        "filter('lsbf', ...): tau/xdt/fpr_tolerance are "
                        "Xling XDT knobs — LSBF answers the fixed "
                        "'any neighbor' question (theta= is its knob)")
                return LSBFAdapter(LSBF(self._R, self.metric, **opts))
            raise ValueError(f"filter({spec!r}): unknown filter; expected "
                             "'xling', 'lsbf', 'none', a Filter object, or "
                             "a callable")
        if opts:
            raise ValueError(f"filter(<instance>, **{sorted(opts)}): extra "
                             "constructor params only apply to by-name "
                             "filters")
        if isinstance(spec, XlingFilter) and spec.estimator is None:
            spec.fit(self._R, cache_key=self._exec["cache_key"],
                     mesh=self._exec["mesh"], engine=engine)
        return as_filter(spec, tau=tau, xdt_mode=xdt_mode,
                         fpr_tolerance=fpr_tolerance)

    def _build_verify(self, engine: JoinEngine, base):
        spec, params = self._verify_spec
        base_is_naive = isinstance(base, NaiveJoin)
        if spec == "auto":
            if params:
                raise ValueError("verify('auto') takes no params — name the "
                                 "backend to tune it")
            if base_is_naive:
                return "exact", "exact"
            # the base verifies its own positives: through candidates() +
            # device verification when it has the probe split, through its
            # own query_counts() otherwise (the generic "any loop-based
            # method" fallback — a synchronous host hop, engine.py)
            return base, getattr(base, "name", type(base).__name__)
        if spec == "exact":
            if not base_is_naive:
                raise ValueError(
                    "verify('exact') is the engine's brute-force sweep and "
                    "only composes with search('naive'); with "
                    f"search({getattr(base, 'name', '?')!r}) use "
                    "verify('auto') (the base's own candidates) or name an "
                    "approximate backend")
            if params:
                raise ValueError("verify('exact') takes no params — it has "
                                 "no index to tune")
            return "exact", "exact"
        if isinstance(spec, str):
            if spec in VERIFY_BACKENDS:     # lsh / ivfpq: engine-cached
                # build the index now so its construction cost lands at
                # build time. With explicit params the plan PINS the built
                # instance (another plan sharing this engine can't clobber
                # it); without params the NAME stays the route, so a later
                # `engine.verifier(name, **params)` retune takes effect
                v = engine.verifier(spec, **params)
                # mutable plans keep the NAME as the route: compact()
                # rebuilds the engine-cached index over the merged R, and
                # the by-name lookup resolves to the rebuilt instance —
                # a pinned instance would keep probing the pre-merge
                # tables (engine.py rebuilds from _verifier_params)
                return (spec if self._mutable else
                        (v if params else spec)), spec
            if spec in JOINS and hasattr(JOINS[spec], "candidates"):
                return make_join(spec, self._R, self.metric, **params), spec
            raise ValueError(
                f"verify({spec!r}): unknown backend; expected 'auto', "
                f"'exact', one of {sorted(set(VERIFY_BACKENDS) - {'exact'})}"
                ", a candidate-producing join name, or a Searcher instance")
        if not (hasattr(spec, "candidates") or hasattr(spec, "query_counts")):
            raise ValueError(
                f"verify({type(spec).__name__}): instance must expose "
                "candidates(Q) -> int32 [q, C] (device verification) or "
                "query_counts(Q, eps) -> int32 [q] (host verification)")
        if getattr(spec, "metric", self.metric) != self.metric:
            raise ValueError(
                f"verify({type(spec).__name__}): instance is built for "
                f"metric {getattr(spec, 'metric')!r}, the plan for "
                f"{self.metric!r}")
        if not self._same_R(getattr(spec, "R", self._R)):
            raise ValueError(
                f"verify({type(spec).__name__}): instance is indexed over a "
                "different R than this plan")
        return spec, getattr(spec, "name", type(spec).__name__)

    # -------------------------------------------------------------- build
    def build(self) -> "JoinPlan":
        """Validate the whole configuration and construct the execution
        state (engine, base, filter, verify route). Idempotent; called
        implicitly by `run` / `stream` / `describe`. All configuration
        errors surface here, before any query is served."""
        if self._built is not None:
            return self
        if self.metric not in ("cosine", "l2"):
            raise ValueError(f"metric={self.metric!r}: expected 'cosine' or "
                             "'l2'")
        if self._mutable:
            sspec = self._search_spec[0]
            if sspec != "naive":
                raise ValueError(
                    f"mutable() with search({_spec_name(sspec)!r}): mutable "
                    "plans require search('naive') — an instance or "
                    "registry base indexes its own host copy of R, which "
                    "insert/delete cannot patch; route approximate "
                    "verification through verify('lsh'/'ivfpq') instead "
                    "(engine-cached, rebuilt on compact)")
            vspec = self._verify_spec[0]
            if not (isinstance(vspec, str)
                    and vspec in ("auto",) + VERIFY_BACKENDS):
                raise ValueError(
                    f"mutable() with verify({_spec_name(vspec)!r}): mutable "
                    "plans need a by-name verify spec "
                    f"({('auto',) + VERIFY_BACKENDS}) so compact() can "
                    "rebuild the index over the merged R — a pinned "
                    "instance would keep probing the pre-merge tables")
        topo_spec = self._exec["topology"]
        r_shards = self._exec["r_shards"]
        # resolve early: an unknown topology name fails here, not mid-build
        topology = resolve_topology(topo_spec) if topo_spec is not None \
            else None
        engine = self._exec["engine"]
        if r_shards is not None:
            # r_shards targets a ring placement: requested explicitly, or
            # carried by a shared engine (then it is a pure cross-check)
            ring_target = (getattr(topology, "name", None) == "ring"
                           or (topology is None and engine is not None
                               and engine.topology.name == "ring"))
            if not ring_target:
                raise ValueError(
                    f"on(r_shards={r_shards}): r_shards sizes the ring "
                    "topology's R-sharding axis — it needs "
                    "on(topology='ring') or a shared ring engine")
            if int(r_shards) < 1:
                raise ValueError(f"on(r_shards={r_shards}): must be >= 1")
        if engine is not None:
            if engine.metric != self.metric or not self._same_R(engine._R_host):
                raise ValueError(
                    "on(engine=...): engine is built over a different "
                    f"(R, metric) — engine has |R|={engine.nr}/"
                    f"{engine.metric!r}, plan has |R|={len(self._R)}/"
                    f"{self.metric!r}")
            if (self._exec["mesh"] is not None
                    and engine.mesh is not self._exec["mesh"]):
                raise ValueError(
                    "on(engine=..., mesh=...): a shared engine carries its "
                    "own mesh; either drop mesh= (the engine's placement "
                    "wins) or drop engine= (the plan builds an engine on "
                    "that mesh)")
            if topology is not None and engine.topology.name != topology.name:
                raise ValueError(
                    "on(engine=..., topology=...): a shared engine carries "
                    f"its own placement ({engine.topology.name!r}); either "
                    "drop topology= or drop engine=")
            if r_shards is not None and engine.r_shards != int(r_shards):
                raise ValueError(
                    f"on(engine=..., r_shards={r_shards}): the shared "
                    f"engine shards R {engine.r_shards} way(s)")
        else:
            mesh = self._exec["mesh"]
            r_axis = getattr(topology, "r_axis", "r")
            if topology is not None and topology.name == "ring":
                if mesh is None:
                    if r_shards is None:
                        raise ValueError(
                            "on(topology='ring') needs r_shards=... (the "
                            "plan then builds a make_join_mesh(r=r_shards) "
                            "over the local devices) or an explicit 2-D "
                            f"mesh with an {r_axis!r} axis")
                    if r_axis != "r":
                        raise ValueError(
                            f"on(topology=<ring over {r_axis!r}>): "
                            "make_join_mesh only builds ('r', 'data') "
                            "meshes — pass an explicit mesh carrying the "
                            "custom axis")
                    from repro.launch.mesh import make_join_mesh
                    mesh = make_join_mesh(r=int(r_shards))
                elif (r_shards is not None
                        and int(mesh.shape.get(r_axis, 1)) != int(r_shards)):
                    raise ValueError(
                        f"on(topology='ring', r_shards={r_shards}, "
                        f"mesh=...): the mesh's {r_axis!r} axis has size "
                        f"{int(mesh.shape.get(r_axis, 1))}")
            if mesh is None:
                # adopt an instance base's own engine when it provably
                # owns this plan's (R, metric) AND no conflicting
                # placement was requested — a NaiveJoin base already
                # pinned R on device; a second engine would double
                # residency (an explicit on(mesh=...) still forces a
                # fresh engine on that mesh)
                spec = self._search_spec[0]
                cand = getattr(spec, "engine", None) \
                    if not isinstance(spec, str) else None
                if (cand is not None and cand.metric == self.metric
                        and self._same_R(cand._R_host)
                        and (topology is None
                             or cand.topology.name == topology.name)):
                    engine = cand
            if engine is None:
                engine = JoinEngine(self._R, self.metric, mesh=mesh,
                                    backend=self._exec["backend"],
                                    block=self._exec["block"],
                                    topology=topology or "replicated")
        if self._mutable:
            engine.auto_compact_at = self._auto_compact_at
            self._seen_compactions = engine.n_compactions
        base = self._build_base(engine)
        filt = self._build_filter(engine)
        verify_route, verify_label = self._build_verify(engine, base)
        # resolve the probe placement now (DESIGN.md §11): probe='device'
        # with a route that has no device probe fails HERE with an
        # actionable message, and the 'auto' placement cost (probe-table
        # upload + program build) lands at build time, not in batch 0
        placed = engine.device_probe_for(verify_route, self._exec["probe"])
        self._built = _BuiltPlan(engine=engine, base=base, filter=filt,
                                 verify_route=verify_route,
                                 verify_label=verify_label,
                                 placed_probe=placed)
        self._device_filter_cache.clear()
        return self

    # ----------------------------------------------------------- execution
    def _filter_state(self, eps: float):
        """(predict, threshold) for the fused device filter at this eps, or
        (None, None) when the filter is host-only; cached per eps so the
        XDT calibration cost is paid once per radius, not per batch."""
        f = self._built.filter
        if f is None or not hasattr(f, "device_filter"):
            return None, None
        key = round(float(eps), 9)
        if key not in self._device_filter_cache:
            self._device_filter_cache[key] = f.device_filter(eps) or (None,
                                                                      None)
        return self._device_filter_cache[key]

    def _host_verdicts(self, Q: np.ndarray, eps: float):
        f = self._built.filter
        if f is None:
            return None                     # engine treats None as all-pos
        return np.asarray(f.verdicts(Q, eps), bool)

    def _route_searcher(self):
        """The searcher object behind the verify route ("exact" -> None;
        engine-cached instance for by-name routes)."""
        route = self._built.verify_route
        if route == "exact":
            return None
        if isinstance(route, str):
            return self._built.engine.verifier(route)
        return route

    def _overflow_frac(self) -> Optional[float]:
        """The verify route's build-time candidate-loss budget
        (`LSHJoin.overflow_frac`), or None when the route has none."""
        frac = getattr(self._route_searcher(), "overflow_frac", None)
        return None if frac is None else float(frac)

    def _wrap(self, res, n: int, eps: float) -> JoinResult:
        st = self._built
        return JoinResult(
            counts=res.counts, n_queries=n, n_searched=res.n_searched,
            meta={"eps": eps, "tau": getattr(st.filter, "tau", 0),
                  "base": getattr(st.base, "name", "?"),
                  "filter": _filter_label(st.filter),
                  "engine": True, "verify": res.verify,
                  "probe": res.probe,
                  "overflow_frac": self._overflow_frac()})

    def run(self, Q: np.ndarray, eps: float) -> JoinResult:
        """One synchronous join pass: fused filter (or uploaded host
        verdicts) -> compact -> verify through the engine. Under
        `on(plan="auto")` the first call plans (measure-then-choose,
        DESIGN.md §16) and every call delegates to the chosen plan.
        The call is the span `join.run`."""
        if self._exec["plan"] == "auto":
            return self._planned_delegate(Q, eps).run(Q, eps)
        self.build()
        Q = np.asarray(Q, np.float32)
        with _span("join.run"):
            predict, threshold = self._filter_state(eps)
            verdicts = (None if predict is not None
                        else self._host_verdicts(Q, eps))
            res = self._built.engine.filtered_join(
                Q, float(eps), predict=predict, threshold=threshold,
                verdicts=verdicts, block=self._exec["block"],
                verify=self._built.verify_route, probe=self._exec["probe"])
            return self._wrap(res, len(Q), eps)

    def stream(self, batches: Iterable[np.ndarray], eps: float, *,
               depth: Optional[int] = None) -> Iterator[JoinResult]:
        """Serving form: yield one JoinResult per query batch, in order,
        through the engine's asynchronous double-buffered pipeline
        (DESIGN.md §5) — batch k+1's programs dispatch while batch k's
        results transfer back; `depth` bounds the in-flight queue
        (`depth=0` ~= synchronous). Bit-identical to per-batch `run`."""
        sess = self.session(eps, depth=depth)
        for Q in batches:
            yield from sess.submit(Q)
        yield from sess.flush()

    def session(self, eps: float, *,
                depth: Optional[int] = None) -> "PlanSession":
        """Open a push-interface serving session at a fixed radius: the
        caller-driven form of `stream` (the serve gateway submits coalesced
        batches as they form rather than pulling from one iterable,
        DESIGN.md §14). Returns a `PlanSession` — `submit(Q)` /
        `flush()` yield `JoinResult`s in FIFO order, bit-identical to
        per-batch `run`; `set_depth()` retargets the in-flight bound
        mid-stream. `depth=None` uses the planner-chosen depth on
        planner-produced plans and 2 otherwise; under `on(plan="auto")`
        the session opens on the planner-chosen delegate."""
        if self._exec["plan"] == "auto":
            return self._planned_delegate(None, eps).session(eps,
                                                             depth=depth)
        if depth is None:
            depth = self._planned_depth or 2
        return PlanSession(self, eps, depth=depth)

    # ------------------------------------------------------ auto-planning
    def _planned_delegate(self, Q, eps: float) -> "JoinPlan":
        """The planner-chosen plan backing `on(plan="auto")` — planned at
        the first run/session against that call's queries and radius,
        then reused for the plan's lifetime (builders reset it)."""
        if self._mutable:
            raise RuntimeError(
                "on(plan='auto') on a mutable plan would leave this handle "
                "mutating a different engine than the one serving queries — "
                "call plan.auto(eps) explicitly and mutate the returned "
                "plan (DESIGN.md §16)")
        if self._auto_delegate is None:
            self._auto_delegate = self.auto(eps, Q)
        return self._auto_delegate

    def auto(self, eps: float, Q: Optional[np.ndarray] = None, *,
             recall: float = 0.9, err: float = 0.1,
             confidence: float = 0.95, seed: int = 0) -> "JoinPlan":
        """Measure-then-choose (DESIGN.md §16): return a new frozen,
        fully-specified `JoinPlan` picked by the cost-based planner for
        this plan's R at radius `eps`.

        The planner draws an error-bounded query sample from `Q` (or
        from R itself when `Q` is None — the serve gateway's query-free
        path), measures selectivity / filter skip rate / LSH bucket
        skew / delta occupancy with cheap probe-free programs, prices a
        pruned candidate grid with BENCH-calibrated constants, and
        applies the winner — splitting hot LSH buckets (skew-aware
        re-bucketing) when the measured occupancy trips the overflow
        trigger. Explicit knobs on THIS plan (`on(topology= ...)`,
        `on(probe=...)`, a by-name `verify(...)`, a shared engine) are
        respected as pinned constraints. `recall` is the acceptance
        floor gating approximate verifies (1.0 forces the exact sweep);
        `err`/`confidence` set the Hoeffding sample bound; `seed` makes
        the whole pass deterministic. The returned plan carries the
        machine-readable rationale in `explain()` and reports it under
        `describe()["planner"]`."""
        from repro.core import planner as _planner
        chosen, explain = _planner.plan_auto(
            self, Q, float(eps), recall=recall, err=err,
            confidence=confidence, seed=seed)
        chosen._exec["plan"] = None         # the choice is final: no
        chosen._planner_explain = explain   # recursive re-planning
        return chosen

    def explain(self) -> dict:
        """The planner's machine-readable rationale for this plan:
        measured workload/skew stats, calibrated cost constants,
        per-candidate cost estimates, rejection reasons, and the chosen
        configuration. Only planner-produced plans carry one — call
        `plan.auto(eps, Q)` (or run once under `on(plan="auto")` and
        take `describe()["planner"]`)."""
        if self._planner_explain is not None:
            return json.loads(json.dumps(self._planner_explain))
        if self._auto_delegate is not None:
            return self._auto_delegate.explain()
        raise RuntimeError(
            "explain(): this plan was not produced by the auto-planner — "
            "call plan.auto(eps, Q) for a planned plan, or run once under "
            "on(plan='auto') (DESIGN.md §16)")

    # ------------------------------------------------------------ sharing
    def fork(self) -> "JoinPlan":
        """A new frozen plan sharing this plan's built engine — the
        multi-tenant form of `on(engine=...)` (DESIGN.md §14): one pinned
        device-resident R/estimator, many plans differing only in
        verify/probe/filter knobs. The fork starts as a copy of this
        plan's filter/search/verify specs and exec placement with
        `engine=` set to the built engine (mesh/topology/r_shards are
        carried BY the engine, so they are cleared on the fork); override
        what differs with the normal builders, then `build()`.

        A by-name `filter("xling", ...)` is carried over as the already-
        FITTED `XlingFilter` instance, so per-tenant tau/xdt retunes
        re-calibrate the threshold without re-fitting the estimator.
        Mutability is NOT inherited: forks are frozen views — mutate
        through the original plan (forks observe inserts/deletes/compacts
        through the shared engine)."""
        self.build()
        clone = JoinPlan(self._R, self.metric)
        fspec, fopts = self._filter_spec
        if fspec == "xling":
            knobs = {k: v for k, v in fopts.items()
                     if k in ("tau", "xdt", "xdt_mode", "fpr_tolerance")}
            clone._filter_spec = (self._built.filter.filt, knobs)
        else:
            clone._filter_spec = (fspec, dict(fopts))
        clone._search_spec = (self._search_spec[0],
                              dict(self._search_spec[1]))
        clone._verify_spec = (self._verify_spec[0],
                              dict(self._verify_spec[1]))
        clone._exec = dict(self._exec)
        clone._exec.update(engine=self._built.engine, mesh=None,
                           topology=None, r_shards=None)
        return clone

    # ------------------------------------------------------------ mutation
    def _require_mutable(self, op: str) -> JoinEngine:
        if not self._mutable:
            raise RuntimeError(
                f"{op}: this plan is frozen — call .mutable() before "
                "insert/delete/compact (DESIGN.md §13)")
        return self.build()._built.engine

    def _sync_after_mutation(self) -> None:
        """Re-sync plan-side state after a mutation that may have
        compacted (explicitly or via the auto_compact_at policy):
        compaction re-uploads R and rebuilds the verifier indices, so the
        plan's host R reference and the resolved probe placement (which
        pins the pre-compact tables) must be refreshed."""
        eng = self._built.engine
        if eng.n_compactions == self._seen_compactions:
            return
        self._seen_compactions = eng.n_compactions
        self._R = eng._R_host
        self._built.placed_probe = eng.device_probe_for(
            self._built.verify_route, self._exec["probe"])

    def insert(self, rows) -> np.ndarray:
        """Insert rows into the logical index set: int64 ids [k] assigned
        to the new rows. They land in the device-resident delta shard and
        participate in every subsequent run/stream batch exactly
        (DESIGN.md §13); `compact()` — or the auto_compact_at policy —
        merges them into the pinned R."""
        eng = self._require_mutable("insert()")
        ids = eng.insert(rows)
        self._sync_after_mutation()
        return ids

    def delete(self, ids) -> None:
        """Delete rows by id (ids from `insert()`, or 0..|R|-1 for the
        original rows). Main-set rows become tombstones — zeroed on
        device and masked out of every verify backend; delta rows are
        dropped in place. Unknown or already-deleted ids raise KeyError
        before any mutation is applied."""
        eng = self._require_mutable("delete()")
        eng.delete(ids)
        self._sync_after_mutation()

    def compact(self) -> dict:
        """Merge the delta into the pinned R and drop tombstones: clears
        the engine's program caches, re-uploads the merged R under the
        plan's topology, rebuilds engine-cached verifier indices, and
        re-resolves the probe placement. Results are unchanged (the
        logical set is the same); per-query cost returns to the pinned
        baseline. Returns the engine's compaction stats."""
        eng = self._require_mutable("compact()")
        stats = eng.compact()
        self._sync_after_mutation()
        return stats

    # ---------------------------------------------------------- inspection
    def describe(self) -> dict:
        """Serializable plan summary (spec + resolved execution state),
        printed by the serve CLI and recorded by the benchmarks. Builds
        the plan if needed (so the summary reflects what will run)."""
        self.build()
        st = self._built

        def scalars(d: dict) -> dict:
            # json-serializable subset (np scalars etc. are coerced or
            # dropped so json.dumps never chokes on a plan summary)
            return {k: (v.item() if isinstance(v, np.generic) else v)
                    for k, v in d.items()
                    if isinstance(v, (int, float, str, bool, np.generic))}

        fspec, fopts = self._filter_spec
        sspec, sparams = self._search_spec
        vspec, vparams = self._verify_spec
        mesh = st.engine.mesh               # the placement that actually runs
        return {
            "metric": self.metric,
            "n_index": int(len(self._R)),
            "dim": int(self._R.shape[1]),
            "filter": {"spec": _spec_name(fspec) if fspec else None,
                       "resolved": _filter_label(st.filter),
                       "tau": getattr(st.filter, "tau", 0),
                       "opts": scalars(fopts)},
            "search": {"spec": _spec_name(sspec),
                       "resolved": getattr(st.base, "name",
                                           type(st.base).__name__),
                       "exact": bool(getattr(st.base, "exact", False)),
                       # False when an explicit verify backend bypasses the
                       # base's own verification route (the filter still
                       # gates which queries reach that backend)
                       "active": (st.verify_route is st.base
                                  or (st.verify_route == "exact"
                                      and isinstance(st.base, NaiveJoin))),
                       "params": scalars(sparams)},
            "verify": {"spec": _spec_name(vspec),
                       "resolved": st.verify_label,
                       "params": scalars(vparams),
                       # the route's build-time candidate-loss budget
                       # (LSH bucket-capacity overflow) — None when the
                       # route tracks none
                       "overflow_frac": self._overflow_frac()},
            "exec": {"backend": st.engine.backend,
                     "block": self._exec["block"],
                     "mesh": (None if mesh is None
                              else dict(zip(mesh.axis_names,
                                            map(int, mesh.devices.shape)))),
                     "engine_shared": self._exec["engine"] is not None,
                     # the placement that actually runs (DESIGN.md §10):
                     # per_device_r_bytes is the number topology moves
                     "topology": {
                         "name": st.engine.topology.name,
                         "r_shards": int(st.engine.r_shards),
                         "per_device_r_bytes":
                             int(st.engine.per_device_r_bytes)},
                     # where the verify route's index probe runs (§11):
                     # "device" with table residency, "host" for probing
                     # routes without a device probe, None for the exact
                     # sweep (it has no probe stage)
                     "probe": {
                         "mode": self._exec["probe"],
                         "resolved": (
                             "device" if st.placed_probe is not None
                             else ("host" if self._route_searcher()
                                   is not None else None)),
                         "table_bytes_per_device": (
                             None if st.placed_probe is None else
                             int(st.placed_probe.table_bytes_per_device)),
                         "cand_width": (
                             None if st.placed_probe is None else
                             int(st.placed_probe.cand_width))}},
            # dynamic-R state (DESIGN.md §13): None for frozen plans
            "mutable": (None if not self._mutable else {
                "auto_compact_at": self._auto_compact_at,
                "n_delta": int(st.engine.n_delta),
                "delta_capacity": int(st.engine.delta_capacity),
                "delta_frac": float(st.engine.delta_frac),
                "n_tombstones": int(st.engine.n_tombstones),
                "compactions": int(st.engine.n_compactions)}),
            # the auto-planner's rationale (DESIGN.md §16): None unless
            # this plan was produced by auto(); the full machine-readable
            # record is plan.explain()
            "planner": (None if self._planner_explain is None else {
                "chosen": dict(self._planner_explain["chosen"]),
                "calibration":
                    self._planner_explain["constants"]["calibration"],
                "sample": dict(self._planner_explain["sample"]),
                "rejected": [dict(r)
                             for r in self._planner_explain["rejected"]],
            }),
        }

    @property
    def engine(self) -> JoinEngine:
        """The plan's `JoinEngine` (builds the plan on first access) —
        the tuning hook for verifier indices lives here
        (`plan.engine.verifier(name, **params)`)."""
        return self.build()._built.engine

    @property
    def base(self):
        """The plan's base Searcher (builds the plan on first access)."""
        return self.build()._built.base


class PlanSession:
    """Caller-driven serving session over a built `JoinPlan` at one radius
    (`JoinPlan.session`): the push form of `stream`, wrapping the engine's
    `StreamSession` with the plan's filter (fused device form, or host
    verdicts computed per submit) and verify route. `submit(Q)` returns
    the (possibly empty) list of OLDER batches' `JoinResult`s released
    under the depth bound; `flush()` is the drain barrier. Results are
    FIFO and bit-identical to per-batch `JoinPlan.run` — the contract the
    serve gateway's scatter-back relies on (DESIGN.md §14)."""

    def __init__(self, plan: JoinPlan, eps: float, *, depth: int = 2):
        plan.build()
        self._plan = plan
        self.eps = float(eps)
        self._predict, self._threshold = plan._filter_state(eps)
        self._sess = plan._built.engine.stream_session(
            eps, predict=self._predict, threshold=self._threshold,
            verify=plan._built.verify_route, depth=depth,
            block=plan._exec["block"], probe=plan._exec["probe"])
        self._pending: list[int] = []   # FIFO batch sizes

    def _emit(self, results) -> list[JoinResult]:
        return [self._plan._wrap(res, self._pending.pop(0), self.eps)
                for res in results]

    def submit(self, Q: np.ndarray) -> list[JoinResult]:
        """Feed one query batch; returns older batches' results whose
        readback completed under the depth bound (host filter verdicts are
        computed here when the filter has no device form).  The call is
        the span `join.submit`."""
        Q = np.asarray(Q, np.float32)
        with _span("join.submit"):
            verdicts = (None if self._predict is not None
                        else self._plan._host_verdicts(Q, self.eps))
            self._pending.append(len(Q))
            return self._emit(self._sess.submit(Q, verdicts=verdicts))

    def flush(self) -> list[JoinResult]:
        """Drain barrier: all remaining results, in submission order.
        The call is the span `join.flush`."""
        with _span("join.flush"):
            return self._emit(self._sess.flush())

    def set_depth(self, depth: int) -> None:
        """Retarget the in-flight bound mid-stream (adaptive depth,
        DESIGN.md §14); takes effect on the next submit."""
        self._sess.set_depth(depth)

    @property
    def depth(self) -> int:
        """The current in-flight bound."""
        return self._sess.depth
