"""Worked example: async double-buffered serving with an LSH verifier,
declared as one `JoinPlan` (DESIGN.md §9).

End-to-end walkthrough of the DESIGN.md §5 pipeline, in three acts:

  1. Declare + build the plan: `.filter("xling", ...)` fits the filter on
     the corpus R, `.search("naive")` makes the exact sweep the base,
     `.verify("lsh", ...)` builds the engine's LSH verifier index with
     tuned parameters, and `.build()` validates the whole combination and
     pins R on device once.
  2. Serve a query stream: `plan.stream(batches, eps, depth=2)` stages
     batch k+1's device programs while batch k's verification results
     transfer back — the bounded in-flight queue keeps at most `depth`
     committed batches outstanding and the generator drains as a flush
     barrier.
  3. Measure quality: per-batch skip rate (filter effectiveness) and
     recall of LSH verification against the engine's exact sweep.

    PYTHONPATH=src python examples/stream_lsh_verify.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import JoinPlan
from repro.data import load_dataset

EPS, TAU = 0.45, 5
BATCH = 256

# ---- 1. declare + build the plan ------------------------------------------
R, S, spec = load_dataset("glove", n=4000)
print(f"corpus R={R.shape}, queries S={S.shape}, metric={spec.metric}")

plan = (JoinPlan(R, spec.metric)
        .filter("xling", tau=TAU, xdt="fpr", fpr_tolerance=0.05,
                estimator="nn", epochs=8)
        .search("naive")
        .verify("lsh", k=14, l=12, n_probes=6)   # tuned verifier index
        .on(backend="jnp")
        .build())                                # validate + fit + pin R
print("plan:", plan.describe()["verify"])

# the engine's exact sweep doubles as the recall oracle
engine = plan.engine

# ---- 2. stream query batches through the async pipeline -------------------
batches = [S[i:i + BATCH] for i in range(0, len(S), BATCH)]
results = list(plan.stream(batches, EPS, depth=2))

# ---- 3. per-batch report: skip rate + recall vs the exact sweep -----------
total_true = total_found = 0
for b, res in enumerate(results):
    true = engine.range_count(batches[b], EPS)          # exact oracle
    found = np.minimum(res.counts, true).sum()
    total_true += true.sum()
    total_found += found
    recall = found / max(true.sum(), 1)
    print(f"batch {b}: queries={len(batches[b])} "
          f"searched={res.n_searched} "
          f"skipped={1 - res.n_searched / len(batches[b]):.2%} "
          f"recall={recall:.3f}")

print(f"stream recall vs exact sweep: "
      f"{total_found / max(total_true, 1):.3f} "
      f"({len(results)} batches, verify=lsh, depth=2)")
