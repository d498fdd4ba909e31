"""Chip smoke check: the join system's main path, end to end, on a TPU.

    python chip_smoke.py [--seed N]      # one chip: phases 1-4
    python chip_smoke.py --chips 4       # four chips: the sharded phase only

Everything runs in this one process (a chip belongs to one process), on
inputs drawn from `--seed` by `repro.data.synthetic.generate`; nothing is
read from `.cache/` (the run points `REPRO_CACHE` at a directory it
creates and removes).  The oracle is `kernels.ref` under
`jax.default_matmul_precision("highest")`.

  1. platform  — print the device; exit non-zero unless JAX runs on a
     TPU with the requested number of chips.  There is no CPU fallback.
  2. xjoin     — the paper's evaluation sample of the glove-shaped corpus
     (d=200, cosine, n=150,000 split 8:2): `JoinPlan` with the Xling
     filter (ground-truth sweep on the Pallas `range_count` kernel),
     `run`, the exact route, a depth-2 stream under
     `host_sync_guard("n_pos", "result")`, and `verify("lsh")` with the
     device probe (the Pallas `lsh_gather` kernel), whose counts must
     equal the same index probed on the host.  The device's running peak
     is read after every step, and inside the Xling fit after each of
     its sub-steps.
  3. resident  — `JoinEngine` over R = 2,000,000 x 200 f32 (about 1.6 GB),
     the exact Pallas sweep for 4,096 queries, checked against the oracle
     on 512 of them; reports the device's peak bytes in use.
  4. gateway   — the serve CLI's `build_gateway` with an exact tenant and
     an LSH tenant probing on device; every ticket equals
     `gw.plan(name).run(...)`.

With `--chips 4` only the sharded phase runs: R = 8,000,000 x 200 f32
under `topology="ring"` (r_shards=4) and `topology="replicated"` on the
four-chip data mesh — the exact sweep, the exact join route and the LSH
device probe, bit-identical between the two placements, the device
probe equal to the host probe, and all equal to the oracle on a query
slice, with R checked to be split over the four chips.

Each phase prints one JSON line; the last line of standard output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
and is printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
EPS = 0.45              # the paper's middle evaluation radius (cosine)
PAPER_N = 150_000       # the paper's evaluation sample, split 8:2
RESIDENT_N = 2_000_000  # resident R of phase 3: ~1.6 GB of f32 at d=200
SHARDED_N = 8_000_000   # sharded R of the four-chip phase: ~6.4 GB


class SmokeFailure(AssertionError):
    """A phase produced a result that contradicts its reference."""


def log(**fields) -> None:
    """One JSON line on stdout."""
    print(json.dumps(fields, default=float), flush=True)


def check(ok: bool, what: str) -> None:
    """Raise `SmokeFailure` unless `ok`."""
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    """The platform, device kind and device count JAX reports."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from its
    monitoring events (a persistent-cache hit skips the backend part)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.total += secs


def peak_device_bytes():
    """Largest `peak_bytes_in_use` over the local devices since the
    process started (None where the backend reports no memory stats)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else max(peaks)


class PeakLog:
    """The device's running peak read after named steps.  `grew` lists
    `(step, peak_bytes)` each time the peak rose, so the step after
    which the final peak was first seen is its last entry."""

    def __init__(self):
        self.grew: list = []

    def mark(self, step: str) -> None:
        peak = peak_device_bytes()
        if peak is not None and (not self.grew or peak > self.grew[-1][1]):
            self.grew.append((step, peak))

    @contextlib.contextmanager
    def inside(self, targets: dict):
        """While open, also `mark` after every call of the functions in
        `targets` (step name -> (owner, attribute name))."""
        from unittest import mock
        with contextlib.ExitStack() as stack:
            for step, (owner, name) in targets.items():
                fn = getattr(owner, name)

                @functools.wraps(fn)
                def marked(*a, _fn=fn, _step=step, **kw):
                    out = _fn(*a, **kw)
                    self.mark(_step)
                    return out

                stack.enter_context(mock.patch.object(owner, name, marked))
            yield self


def xling_fit_steps() -> dict:
    """The sub-steps of the Xling fit (the estimator is the default RMI)
    that `PeakLog.inside` marks: the ground-truth sweep, the training
    tuples, each network's training, each network's predictions over the
    tuples, and the XDT calibration's predictions."""
    from repro.core import atcs, xling
    from repro.models import rmi
    return {"ground_truth": (xling, "cardinality_table"),
            "training_tuples": (atcs, "build_training_tuples"),
            "fit_network": (rmi, "fit_regressor"),
            "fit_predict": (rmi, "_fit_predict"),
            "xdt_predictions": (xling.XlingFilter, "_train_predictions")}


def oracle_counts(Q, R, eps_grid, metric: str = "cosine", *,
                  q_chunk: int = 2048, r_chunk: int = 1 << 18) -> np.ndarray:
    """int32 [q, m]: `ref.range_count_hist` at highest matmul precision,
    summed over R chunks (and Q chunks) so the distance block stays
    bounded whatever |R| is."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    hist = jax.jit(ref.range_count_hist, static_argnames="metric")
    eps = jnp.asarray(np.asarray(eps_grid, np.float32))
    out = np.zeros((len(Q), len(eps)), np.int64)
    with jax.default_matmul_precision("highest"):
        for r0 in range(0, len(R), r_chunk):
            rc = jnp.asarray(R[r0:r0 + r_chunk])
            for q0 in range(0, len(Q), q_chunk):
                qc = jnp.asarray(Q[q0:q0 + q_chunk])
                out[q0:q0 + q_chunk] += np.asarray(hist(qc, rc, eps,
                                                        metric=metric))
            del rc
    return out.astype(np.int32)


def mosaic_kernel_in(compiled_text: str) -> bool:
    """True when a compiled program holds a Pallas TPU (Mosaic) kernel —
    the proof that a kernel was compiled rather than interpreted."""
    return "tpu_custom_call" in compiled_text


def sweep_hlo(eng, Q, eps_grid) -> str:
    """Compiled text of the engine's range-count sweep at this batch's
    shapes (the program `range_count_hist` dispatches)."""
    import jax.numpy as jnp
    from repro.core import engine as em
    prog = em._hist_program(eng.mesh, eng.data_axis, eng.backend, eng.metric,
                            eng.block_q, eng.block_r, eng.eps_chunk, eng.nr,
                            eng.topology)
    qdev = eng._put_q(eng._pad_q(Q))
    ep = jnp.asarray(eng._pad_eps(eps_grid))
    return prog.lower(qdev, eng._Rdev, ep, eng._nrv_dev).compile().as_text()


def probe_hlo(placed, d: int, rows: int = 256) -> str:
    """Compiled text of a placed device probe for a `rows`-query batch."""
    import jax
    import jax.numpy as jnp
    qpos = jax.ShapeDtypeStruct((rows, d), jnp.float32)
    return placed._probe_fn.lower(qpos, *placed._state).compile().as_text()


def paper_corpus(seed: int, n: int = PAPER_N):
    """(R, S): the glove-shaped corpus drawn from `seed`, split 8:2."""
    from repro.data import generate
    x = generate("glove", n, seed)
    n_r = int(0.8 * n)
    return x[:n_r], x[n_r:]


# ================================================================ phases
def lsh_index_stats(plan) -> dict:
    """Sizing and build-time overflow of a built plan's LSH index."""
    lsh = plan._route_searcher()
    desc = plan.describe()
    return {"n_buckets": int(lsh.n_buckets), "cap": int(lsh.cap),
            "overflow_frac": desc["verify"]["overflow_frac"],
            "table_bytes_per_device":
                desc["exec"]["probe"]["table_bytes_per_device"]}


def phase_xjoin(R, S, clock: CompileClock, *, eps: float = EPS,
                batch: int = 256) -> dict:
    """Phase 2: paper-scale XJoin through `JoinPlan`, against the oracle."""
    from repro.core import JoinPlan
    from repro.core import engine as em

    peaks = PeakLog()
    c0, t0 = clock.total, time.perf_counter()
    with peaks.inside(xling_fit_steps()):
        plan = (JoinPlan(R, "cosine").filter("xling", tau=5, xdt="fpr")
                .search("naive").on(backend="auto").build())
    build_s = time.perf_counter() - t0
    peaks.mark("build")
    truth = oracle_counts(S, R, [eps])[:, 0]
    peaks.mark("oracle")

    t0 = time.perf_counter()
    res = plan.run(S, eps)
    first_run_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = plan.run(S, eps)
    run_s = time.perf_counter() - t0
    peaks.mark("run")
    exact = plan.fork().filter("none").build().run(S, eps)
    peaks.mark("exact_route")

    batches = [S[i:i + batch] for i in range(0, len(S), batch)]
    t0 = time.perf_counter()
    with em.host_sync_guard("n_pos", "result"):
        streamed = list(plan.stream(batches, eps, depth=2))
    stream_s = time.perf_counter() - t0
    stream_counts = np.concatenate([r.counts for r in streamed])
    peaks.mark("stream")

    lsh_plan = plan.fork().verify("lsh").on(probe="device").build()
    lres = lsh_plan.run(S, eps)
    peaks.mark("lsh_device_probe")
    # the same index probed on the host: the device probe (the Pallas
    # gather) must hand verification exactly the same candidate sets
    hres = lsh_plan.fork().on(probe="host").build().run(S, eps)
    placed = lsh_plan._built.placed_probe
    out = {
        "phase": "xjoin", "n_r": len(R), "n_s": len(S), "d": R.shape[1],
        "eps": eps, "backend": plan.engine.backend,
        "build_s": build_s, "first_run_s": first_run_s, "run_s": run_s,
        "stream_s": stream_s, "compile_s": clock.total - c0,
        "skip_frac": 1.0 - res.n_searched / len(S),
        "xjoin_recall": res.recall_vs(truth),
        "exact_mismatches": int(np.sum(exact.counts != truth)),
        # a filtered query is either skipped (count 0) or counted exactly
        "xjoin_mismatches": int(np.sum((res.counts != truth)
                                       & (res.counts != 0))),
        "stream_equals_run": bool(np.array_equal(stream_counts, res.counts)),
        "lsh_probe": lres.meta["probe"], "lsh_host_probe": hres.meta["probe"],
        "lsh_device_vs_host_mismatches": int(np.sum(lres.counts
                                                    != hres.counts)),
        "lsh_index": lsh_index_stats(lsh_plan),
        "lsh_recall": lres.recall_vs(truth),
        # verified candidates can only under-count
        "lsh_over_counts": int(np.sum(lres.counts > truth)),
        "lsh_kernel_compiled": (placed is not None and mosaic_kernel_in(
            probe_hlo(placed, R.shape[1]))),
        "peak_grew": peaks.grew,
        "peak_bytes_in_use": peak_device_bytes(),
    }
    log(**out)
    check(out["exact_mismatches"] == 0, "exact route disagrees with ref")
    check(out["xjoin_mismatches"] == 0, "XJoin counts disagree with ref")
    check(out["stream_equals_run"], "stream differs from run")
    check(out["lsh_probe"] == "device" and out["lsh_host_probe"] == "host",
          "LSH routes did not probe where asked")
    check(out["lsh_device_vs_host_mismatches"] == 0,
          "LSH device probe disagrees with the host probe")
    check(out["lsh_over_counts"] == 0, "LSH counted past ref")
    return out


def phase_resident(seed: int, clock: CompileClock, *, n_r: int = RESIDENT_N,
                   n_q: int = 4096, n_check: int = 512,
                   eps_grid=(0.4, 0.45, 0.5)) -> dict:
    """Phase 3: exact Pallas sweep over an R a deployment would keep
    resident, against the oracle on a query slice."""
    from repro.core import JoinEngine
    from repro.data import generate

    x = generate("glove", n_r + n_q, seed + 1)
    R, Q = x[:n_r], x[n_r:]
    del x
    c0, t0 = clock.total, time.perf_counter()
    eng = JoinEngine(R, "cosine", backend="pallas")
    upload_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    counts = eng.range_count_hist(Q, eps_grid)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    counts = eng.range_count_hist(Q, eps_grid)
    sweep_s = time.perf_counter() - t0
    truth = oracle_counts(Q[:n_check], R, eps_grid)
    out = {
        "phase": "resident", "n_r": n_r, "n_q": n_q, "d": R.shape[1],
        "eps_grid": list(eps_grid), "backend": eng.backend,
        "r_bytes": int(eng.per_device_r_bytes), "upload_s": upload_s,
        "first_sweep_s": first_s, "sweep_s": sweep_s,
        "compile_s": clock.total - c0,
        "mismatches": int(np.sum(counts[:n_check] != truth)),
        "checked_queries": n_check,
        "sweep_kernel_compiled": mosaic_kernel_in(
            sweep_hlo(eng, Q, eps_grid)),
        "peak_bytes_in_use": peak_device_bytes(),
    }
    log(**out)
    check(out["mismatches"] == 0, "resident sweep disagrees with ref")
    return out


def phase_gateway(R, S, clock: CompileClock, *, eps: float = EPS,
                  n_batches: int = 3, batch: int = 256) -> dict:
    """Phase 4: requests through the serve CLI's gateway, two tenants."""
    from repro.launch.serve import build_gateway, build_parser

    args = build_parser().parse_args([
        "--dataset", "glove", "--n", str(len(R) + len(S)),
        "--eps", str(eps), "--tau", "5", "--verify", "exact",
        "--tenant", f"name=lsh,eps={eps},verify=lsh,probe=device"])
    c0, t0 = clock.total, time.perf_counter()
    gw = build_gateway(args, R, "cosine")
    build_s = time.perf_counter() - t0
    names = sorted(gw.report()["tenants"])
    tickets = []
    t0 = time.perf_counter()
    for b in range(n_batches):
        q = S[b * batch:(b + 1) * batch]
        for name, part in zip(names, np.array_split(q, len(names))):
            tickets.append((name, part, gw.submit(name, part)))
    gw.flush()
    serve_s = time.perf_counter() - t0
    mismatches = sum(
        int(np.sum(t.counts != gw.plan(name).run(q, t.eps).counts))
        for name, q, t in tickets)
    out = {
        "phase": "gateway", "tenants": {
            n: gw.report()["tenants"][n]["verify"] for n in names},
        "requests": len(tickets), "build_s": build_s, "serve_s": serve_s,
        "compile_s": clock.total - c0, "mismatches": mismatches,
        "peak_bytes_in_use": peak_device_bytes(),
    }
    log(**out)
    check(mismatches == 0, "gateway tickets differ from plan.run")
    return out


def default_lsh_sizing(R, lsh) -> dict:
    """What `LSHJoin`'s default sizing would allocate for R with `lsh`'s
    hash functions: 2**ceil(log2 n) buckets, a capacity at the p99.9
    bucket occupancy, and the dense [l, B, cap] int32 tables."""
    from repro.core.probe import lsh_bucket_ids, lsh_hash_codes
    nb = max(256, 2 ** int(np.ceil(np.log2(len(R)))))
    codes = lsh_hash_codes(R, lsh.proj, lsh.bias, metric=lsh.metric,
                           W=lsh.W)
    buckets = lsh_bucket_ids(codes, lsh.salt, nb)
    del codes
    occ = np.stack([np.bincount(buckets[:, t], minlength=nb)
                    for t in range(lsh.l)])
    cap = int(max(2, np.quantile(occ.reshape(-1), 0.999)))
    return {"n_buckets": nb, "cap": cap,
            "table_bytes": int(lsh.l) * nb * cap * 4}


def phase_sharded(seed: int, clock: CompileClock, *, chips: int = 4,
                  n_r: int = SHARDED_N, n_q: int = 2048, n_check: int = 256,
                  eps: float = EPS) -> dict:
    """Four-chip phase: R row-sharded under the ring topology against the
    same R replicated on the data mesh, and both against the oracle."""
    from repro.core import JoinPlan, make_join
    from repro.core.engine import clear_program_cache
    from repro.data import generate
    from repro.launch.mesh import make_data_mesh

    t_start = time.perf_counter()
    x = generate("glove", n_r + n_q, seed + 2)
    R, Q = x[:n_r], x[n_r:]
    del x
    d = R.shape[1]
    log(phase="sharded_data", n_r=n_r, n_q=n_q,
        seconds=time.perf_counter() - t_start)
    t0 = time.perf_counter()
    # one LSH index, probed on device under both placements.  Its default
    # sizing (`default_lsh_sizing`, logged below) is, at 8M rows of this
    # corpus, 2^23 buckets at a p99.9 capacity of 79: about 26.5 GB of
    # int32 tables.  The ring would hold a quarter of that per chip, but
    # "replicated" keeps the whole table on every chip beside the 8.2 GB
    # of stored R, and one chip has 16 GB of HBM.  So the index is cut
    # to an eighth of the default bucket count and a capacity of 32:
    # tables of about 1.3 GB.  Overflowing memberships lower recall,
    # never counts.
    default_buckets = max(256, 2 ** int(np.ceil(np.log2(n_r))))  # LSHJoin's
    lsh = make_join("lsh", R, "cosine", n_buckets=default_buckets // 8,
                    cap=32)
    lsh_build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    default_sizing = default_lsh_sizing(R, lsh)
    log(phase="sharded_lsh", build_s=lsh_build_s,
        n_buckets=int(lsh.n_buckets), cap=int(lsh.cap),
        overflow_frac=float(lsh.overflow_frac),
        table_bytes=int(lsh.tables.nbytes), default_sizing=default_sizing,
        default_sizing_s=time.perf_counter() - t0)
    results, placement = {}, {}
    for topo in ("ring", "replicated"):
        on = (dict(topology="ring", r_shards=chips) if topo == "ring"
              else dict(mesh=make_data_mesh(chips)))
        c0, t0 = clock.total, time.perf_counter()
        plan = (JoinPlan(R, "cosine").filter("none")
                .on(backend="auto", **on).build())
        eng = plan.engine
        build_s = time.perf_counter() - t0
        shards = eng._Rdev.addressable_shards
        rows = eng.nr_padded // eng.r_shards
        text = sweep_hlo(eng, Q, [eps])
        t0 = time.perf_counter()
        sweep = eng.range_count(Q, eps)
        exact = plan.run(Q, eps).counts
        lsh_plan = plan.fork().verify(lsh).on(probe="device").build()
        lres = lsh_plan.run(Q, eps)
        run_s = time.perf_counter() - t0
        hres = lsh_plan.fork().on(probe="host").build().run(Q, eps)
        placement[topo] = {
            "backend": eng.backend, "r_shards": int(eng.r_shards),
            "devices": len({s.device for s in shards}),
            "shard_rows": sorted({int(s.data.shape[0]) for s in shards}),
            "per_device_r_bytes": int(eng.per_device_r_bytes),
            "lsh_table_bytes_per_device": lsh_index_stats(lsh_plan)[
                "table_bytes_per_device"],
            "sweep_kernel_compiled": mosaic_kernel_in(text),
            # the partitioned program's R operand is the per-device shard
            "hlo_r_operand_local": (f"f32[{rows},{d}]" in text and (
                rows == eng.nr_padded
                or f"f32[{eng.nr_padded},{d}]" not in text)),
            "build_s": build_s, "run_s": run_s,
            "compile_s": clock.total - c0, "lsh_probe": lres.meta["probe"],
            "lsh_host_probe": hres.meta["probe"],
            "lsh_device_vs_host_mismatches": int(np.sum(lres.counts
                                                        != hres.counts)),
            "peak_bytes_in_use": peak_device_bytes()}
        log(phase="sharded_placement", topology=topo, **placement[topo])
        results[topo] = (sweep, exact, lres.counts)
        del plan, lsh_plan, eng, shards, lres, hres
        clear_program_cache()
        gc.collect()
    truth = oracle_counts(Q[:n_check], R, [eps])[:, 0]
    ring, rep = results["ring"], results["replicated"]
    out = {
        "phase": "sharded", "chips": chips, "n_r": n_r, "n_q": n_q, "d": d,
        "eps": eps, "lsh_n_buckets": int(lsh.n_buckets),
        "lsh_cap": int(lsh.cap), "lsh_overflow_frac": float(lsh.overflow_frac),
        "placement": placement,
        "ring_equals_replicated": {
            k: bool(np.array_equal(a, b))
            for k, a, b in zip(("sweep", "exact", "lsh"), ring, rep)},
        "sweep_mismatches": int(np.sum(ring[0][:n_check] != truth)),
        "exact_mismatches": int(np.sum(ring[1][:n_check] != truth)),
        "lsh_over_counts": int(np.sum(ring[2][:n_check] > truth)),
        "lsh_recall": float(np.minimum(ring[2][:n_check], truth).sum()
                            / max(int(truth.sum()), 1)),
        "checked_queries": n_check,
        "seconds": time.perf_counter() - t_start,
    }
    log(**out)
    check(all(out["ring_equals_replicated"].values()),
          "ring and replicated counts differ")
    check(out["sweep_mismatches"] == 0 and out["exact_mismatches"] == 0,
          "sharded counts disagree with ref")
    check(out["lsh_over_counts"] == 0, "LSH counted past ref")
    p = placement["ring"]
    check(p["r_shards"] == chips and p["devices"] == chips
          and p["hlo_r_operand_local"], "R is not split over the chips")
    check(all(q["lsh_probe"] == "device" and q["lsh_host_probe"] == "host"
              for q in placement.values()),
          "LSH routes did not probe where asked")
    check(all(q["lsh_device_vs_host_mismatches"] == 0
              for q in placement.values()),
          "LSH device probe disagrees with the host probe")
    return out


def run_one_chip(seed: int, clock: CompileClock, *, paper_n: int = PAPER_N,
                 resident_n: int = RESIDENT_N, resident_q: int = 4096,
                 resident_check: int = 512, gateway_batches: int = 3) -> list:
    """Phases 2-4 in order; returns their result dicts."""
    R, S = paper_corpus(seed, paper_n)
    out = [phase_xjoin(R, S, clock)]
    out.append(phase_resident(seed, clock, n_r=resident_n, n_q=resident_q,
                              n_check=resident_check))
    gc.collect()
    out.append(phase_gateway(R, S, clock, n_batches=gateway_batches))
    return out


def main(argv=None) -> int:
    """Platform check, then the one-chip phases or the four-chip phase;
    prints the result line last and returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases 1-4 on one chip; 4: the sharded phase "
                         "on a four-chip host")
    args = ap.parse_args(argv)

    info = device_info()
    log(phase="platform", **info)
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{info['platform']!r}); no phase was run", file=sys.stderr)
        return 1
    if info["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{info['count']} TPU device(s)", file=sys.stderr)
        return 1

    cache = tempfile.mkdtemp(prefix="chip_smoke_")
    os.environ["REPRO_CACHE"] = cache       # read nothing from .cache/
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.kernels.ops import _resolve
        from repro.kernels.range_count import default_interpret
        from repro.utils import init_compile_cache

        log(phase="setup", compile_cache=init_compile_cache(),
            auto_backend=_resolve("auto"), interpret=default_interpret())
        check(_resolve("auto") == "pallas" and not default_interpret(),
              "the auto backend does not resolve to compiled Pallas")
        clock = CompileClock()
        if args.chips == 1:
            results = run_one_chip(args.seed, clock)
            check(results[0]["lsh_kernel_compiled"]
                  and results[1]["sweep_kernel_compiled"],
                  "a Pallas kernel was not compiled for the chip")
        else:
            res = phase_sharded(args.seed, clock, chips=args.chips)
            check(all(p["sweep_kernel_compiled"]
                      for p in res["placement"].values()),
                  "a Pallas kernel was not compiled for the chip")
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
