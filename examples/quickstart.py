"""Quickstart: declare an XJoin with JoinPlan, run it, compare vs naive.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys, os, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
from repro.core import JoinPlan, make_join
from repro.data import load_dataset

EPS, TAU, N = 0.45, 5, 8000

print(f"== loading glove-like corpus (n={N}) ==")
R, S, spec = load_dataset("glove", n=N)
print(f"R (indexed) = {R.shape}, S (queries) = {S.shape}, metric = {spec.metric}")

print("\n== building the plan (fits Xling; RMI takes minutes, NN here) ==")
t0 = time.time()
plan = (JoinPlan(R, spec.metric)
        .filter("xling", tau=TAU, xdt="fpr", estimator="nn", epochs=12)
        .search("naive")
        .on(backend="auto", cache_key=("quickstart", N))
        .build())
print(f"offline build: {time.time()-t0:.1f}s "
      f"(ground-truth targets + ATCS + estimator training)")

naive = make_join("naive", R, spec.metric, backend="auto")
naive.query_counts(S, EPS)                       # warm the jit
t0 = time.time(); truth = naive.query_counts(S, EPS); t_naive = time.time() - t0

plan.run(S, EPS)                                 # warm
res = plan.run(S, EPS)
print(f"\n== XJoin vs naive @ eps={EPS}, tau={TAU} ==")
print(f"negative-query portion: {(truth == 0).mean():.2%}")
print(f"queries searched:       {res.n_searched}/{res.n_queries} "
      f"({1 - res.n_searched/res.n_queries:.1%} skipped)")
print(f"naive:  {t_naive*1e3:7.1f} ms   recall 1.000")
print(f"xjoin:  {res.t_total*1e3:7.1f} ms   recall {res.recall_vs(truth):.3f} "
      f"  -> {t_naive/res.t_total:.2f}x speedup")
