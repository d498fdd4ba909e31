"""Serving driver: the multi-tenant gateway CLI (DESIGN.md §14).

The paper's production story end-to-end: the CLI flags compile into a
`repro.serve.Gateway` — one pinned device-resident R/estimator behind
per-tenant `(eps, recall target, latency SLO)` classes — and the query
stream is replayed as per-tenant REQUESTS through the gateway's
admission path: eps-aware result cache, cross-request micro-batching
into the engine's bucketed static shapes, asynchronous pipelined
dispatch with SLO-driven adaptive depth, and per-request scatter-back.

The base flags (--eps/--tau/--verify/--probe/--depth/--slo-ms) define
the "default" tenant class; each repeatable `--tenant` flag adds
another, e.g.

  --tenant "name=gold,eps=0.4,verify=exact,slo_ms=50" \
  --tenant "name=bulk,eps=0.5,verify=lsh,recall=0.9,tau=20"

Requests round-robin over the classes within every input batch. The
first output line is the gateway configuration; each request line
reports result quality (recall vs the exact oracle over the logical
set), cache hits, and latency; the summary aggregates them and the
final line is the full `Gateway.report()` (admitted/coalesced/
cache-hit/SLO-miss counters, p50/p95, per-group stream depths).

  PYTHONPATH=src python -m repro.launch.serve --dataset glove --n 4000 \
      --eps 0.45 --tau 5 --batches 4 --batch-size 256 --verify lsh \
      --tenant "name=strict,eps=0.4,verify=exact,slo_ms=100"
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.data import load_dataset
from repro.serve import Gateway, TenantClass
from repro.utils import init_compile_cache


def batch_stats(b: int, res, true_counts: np.ndarray,
                delta_frac: float | None = None) -> dict:
    """One report line for a `JoinResult`-shaped batch (the single-plan
    serving form, kept for plan-level debugging and the probe tests):
    filter skip rate, verification recall vs the exact oracle, probe
    placement + the verify index's build-time candidate-loss budget
    (DESIGN.md §11), and the delta occupancy at submit time when a
    mutation trace is being replayed (DESIGN.md §13)."""
    out = {
        "batch": b,
        "queries": int(res.n_queries),
        "searched": int(res.n_searched),
        "skipped_frac": 1.0 - res.n_searched / max(res.n_queries, 1),
        "recall": res.recall_vs(true_counts),
        "verify": res.meta.get("verify", "exact"),
        "probe": res.meta.get("probe"),
        "overflow_frac": res.meta.get("overflow_frac"),
    }
    if delta_frac is not None:
        out["delta_frac"] = float(delta_frac)
    return out


def summarize(stats: list[dict], build_s: float) -> dict:
    """Aggregate the per-request lines: mean recall / cache-hit
    fraction, p50/p95 request latency, and the SET of verify backends
    seen across the run — a multi-tenant run mixes routes, so reporting
    one request's backend would misdescribe every other tenant."""
    if not stats:
        return {"build_s": build_s, "requests": 0}
    lat = np.asarray([s["latency_ms"] for s in stats])
    return {
        "build_s": build_s,
        "requests": len(stats),
        "mean_recall": float(np.mean([s["recall"] for s in stats])),
        "mean_cache_hit_frac": float(np.mean(
            [s["cache_hits"] / max(s["queries"], 1) for s in stats])),
        "mean_latency_ms": float(lat.mean()),
        "p50_latency_ms": float(np.percentile(lat, 50)),
        "p95_latency_ms": float(np.percentile(lat, 95)),
        "verify": sorted({s["verify"] for s in stats}),
    }


def load_trace(path: str) -> dict[int, list[dict]]:
    """Parse a JSONL mutation trace into {batch index: [ops]}: each line is
    `{"before_batch": k, "op": "insert"|"delete"|"compact", "n": ...,
    "seed": ...}` — the ops run right before batch k is submitted."""
    by_batch: dict[int, list[dict]] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            op = json.loads(line)
            by_batch.setdefault(int(op.get("before_batch", 0)), []).append(op)
    return by_batch


def apply_ops(target, ops, live: dict, dim: int) -> None:
    """Replay trace ops against a mutable target (a `Gateway` or a
    mutable `JoinPlan` — anything exposing insert/delete/compact),
    mirroring them into `live` (id -> row), the host shadow of the
    logical set that the recall oracle is computed from. Inserts draw
    seeded unit rows; deletes draw seeded ids from the live set (never
    the last row)."""
    for op in ops:
        kind = op["op"]
        rng = np.random.default_rng(int(op.get("seed", 0)))
        if kind == "insert":
            rows = rng.normal(size=(int(op["n"]), dim)).astype(np.float32)
            rows /= np.maximum(
                np.linalg.norm(rows, axis=1, keepdims=True), 1e-12)
            ids = target.insert(rows)
            live.update(zip(map(int, ids), rows))
        elif kind == "delete":
            pool = np.fromiter(live, dtype=np.int64)
            ids = rng.choice(pool, size=min(int(op["n"]), len(pool) - 1),
                             replace=False)
            target.delete(ids)
            for i in ids:
                live.pop(int(i))
        elif kind == "compact":
            target.compact()
        else:
            raise ValueError(f"mutate-trace: unknown op {kind!r}; expected "
                             "'insert', 'delete', or 'compact'")


#: --tenant spec fields -> parser (everything else is an error)
_TENANT_FIELDS = {
    "name": str, "eps": float, "recall": float, "slo_ms": float,
    "verify": str, "probe": str, "tau": int, "depth": int, "max_depth": int,
}


def parse_tenant(spec: str) -> TenantClass:
    """Compile one `--tenant "k=v,k=v,..."` spec into a `TenantClass`
    (fields: name, eps, recall, slo_ms, verify, probe, tau, depth,
    max_depth)."""
    kw: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key = key.strip()
        if key not in _TENANT_FIELDS:
            raise ValueError(f"--tenant {spec!r}: unknown field {key!r}; "
                             f"expected {sorted(_TENANT_FIELDS)}")
        kw[key] = _TENANT_FIELDS[key](val.strip())
    if "name" not in kw or "eps" not in kw:
        raise ValueError(f"--tenant {spec!r}: name= and eps= are required")
    if "recall" in kw:
        kw["recall_target"] = kw.pop("recall")
    return TenantClass(**kw)


def build_gateway(args, R, metric: str) -> Gateway:
    """Compile the CLI flags into a built `Gateway`: the base flags make
    the "default" tenant class, each `--tenant` spec adds one, and the
    shared Xling filter is fitted once at build (so its one-time cost
    lands in build_s, not in request 0's reported latency)."""
    classes = [TenantClass("default", eps=args.eps, verify=args.verify,
                           probe=args.probe, slo_ms=args.slo_ms,
                           depth=args.depth)]
    classes += [parse_tenant(s) for s in args.tenant]
    return Gateway(
        R, classes, metric=metric, filter="xling",
        filter_opts=dict(tau=args.tau, xdt="fpr", estimator=args.estimator,
                         epochs=args.epochs),
        backend="auto", topology=args.topology, r_shards=args.r_shards,
        cache_key=(args.dataset, args.n), eps_quantum=args.eps_quantum,
        max_batch_rows=args.max_batch_rows,
        mutable=args.mutate_trace is not None)


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's flags (`main` parses them; `build_gateway` compiles
    them into a Gateway)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="glove")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--eps", type=float, default=0.45)
    ap.add_argument("--tau", type=int, default=5)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--estimator", default="nn")
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--verify", default="exact",
                    choices=("auto", "exact", "lsh", "ivfpq", "learned"),
                    help="default tenant's verification backend "
                         "(DESIGN.md §5; 'learned' is the RMI index)")
    ap.add_argument("--depth", type=int, default=2,
                    help="default tenant's initial async in-flight bound "
                         "(0 ~= synchronous; adapts under --slo-ms)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="default tenant's per-request latency SLO — "
                         "drives SLO-miss accounting and adaptive depth")
    ap.add_argument("--tenant", action="append", default=[],
                    metavar="SPEC",
                    help="add a tenant class: 'name=gold,eps=0.4,"
                         "verify=lsh,recall=0.9,slo_ms=50,tau=5' "
                         "(repeatable; requests round-robin over classes)")
    ap.add_argument("--eps-quantum", type=float, default=None,
                    help="snap explicit request radii to this grid "
                         "(coalescing/caching buckets, DESIGN.md §14)")
    ap.add_argument("--max-batch-rows", type=int, default=None,
                    help="coalescing budget per dispatched batch "
                         "(default: the engine's minimum padded bucket)")
    ap.add_argument("--topology", default=None,
                    choices=("replicated", "ring"),
                    help="where R lives on the mesh (DESIGN.md §10): "
                         "replicated (default) or ring (R sharded over "
                         "--r-shards devices)")
    ap.add_argument("--r-shards", type=int, default=None,
                    help="ring topology: number of R shards (the mesh's "
                         "r-axis size)")
    ap.add_argument("--probe", default="auto",
                    choices=("auto", "device", "host"),
                    help="where the approximate verify route's index "
                         "probe runs (DESIGN.md §11): auto = on device "
                         "whenever the searcher supports it")
    ap.add_argument("--mutate-trace", default=None, metavar="PATH",
                    help="JSONL mutation trace replayed against the "
                         "stream (DESIGN.md §13): each line "
                         "{'before_batch': k, 'op': 'insert'|'delete'|"
                         "'compact', 'n': ..., 'seed': ...}; makes the "
                         "gateway mutable and computes each request's "
                         "recall oracle against the logical set at "
                         "submit time")
    return ap


def main():
    """CLI entry point: compile the flags into a Gateway, replay the
    query stream as round-robin per-tenant requests, and print the
    per-request lines, aggregate summary, and the gateway report."""
    args = build_parser().parse_args()

    init_compile_cache()
    R, S, spec = load_dataset(args.dataset, n=args.n)
    t0 = time.time()
    gw = build_gateway(args, R, spec.metric)
    build_s = time.time() - t0
    rep0 = gw.report()
    print(json.dumps({"gateway": {k: rep0[k] for k in
                                  ("mutable", "eps_quantum",
                                   "max_batch_rows", "n_index", "tenants")}},
                     default=str))
    names = sorted(rep0["tenants"])
    trace = load_trace(args.mutate_trace) if args.mutate_trace else {}
    live = {i: R[i] for i in range(len(R))}

    def oracle(q: np.ndarray, eps: float) -> np.ndarray:
        # brute force over the logical set at submit time — under a
        # mutation trace `live` tracks inserts/deletes, otherwise it is
        # just R (DESIGN.md §13)
        from repro.kernels import ref
        world = np.stack(list(live.values()))
        return np.asarray(ref.range_count(q, world, eps,
                                          metric=spec.metric))

    batches = [q for b in range(args.batches)
               if len(q := S[b * args.batch_size:(b + 1) * args.batch_size])]
    stats = []
    for b, batch in enumerate(batches):
        apply_ops(gw, trace.get(b, ()), live, R.shape[1])
        # one request per tenant class per input batch (round-robin
        # split); the gateway coalesces compatible ones back together
        parts = [p for p in np.array_split(batch, len(names)) if len(p)]
        tickets = [(name, q, gw.submit(name, q))
                   for name, q in zip(names, parts)]
        gw.flush()
        for name, q, t in tickets:
            stats.append({
                "batch": b, "tenant": name, "queries": int(t.n),
                "eps": t.eps, "cache_hits": int(t.meta["cache_hits"]),
                "recall": float(np.minimum(t.counts, tr := oracle(q, t.eps))
                                .sum() / max(tr.sum(), 1)),
                "latency_ms": float(t.latency_ms),
                "verify": rep0["tenants"][name]["verify"],
            })
            print(json.dumps(stats[-1]))

    print(json.dumps({"summary": summarize(stats, build_s)}))
    print(json.dumps({"report": gw.report()}, default=str))


if __name__ == "__main__":
    main()
