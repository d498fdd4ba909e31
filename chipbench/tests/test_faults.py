"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (`harness.run_cell`: set-up, warm-up,
window, reference, check) at a tiny size on the CPU, skipping only the
look for a chip, with the plan's answers altered where they are produced.
The faults a one-chip join cell can have: answers altered (every 10th
off by one), and half of each batch left out, either never answered
(the answer cut short) or answered 0 in the answer's own shape, as a
verify or compaction that dropped rows would answer.  A sound run of the
same cell comes out correct.

The tiny cell's filter, fitted for one epoch on 2,000 rows, misses far
more true pairs than the cell's own (a third or more against about a
thousandth), so its `missed_pair_frac` limit is placed as the cell's is
on the chip: between the sound reading of the same tiny cell and seed
and what half of each batch left out would read, a quarter of the way
up."""
import dataclasses
import functools

import numpy as np
import pytest

import harness
from conftest import tiny


def alter_answers(res):
    """Every 10th answer off by one: at the tiny size (a pool of 1,500
    queries) still more queries than any cell's limit."""
    counts = np.array(res.counts)
    counts[::10] += 1
    return dataclasses.replace(res, counts=counts)


def leave_out_half(res):
    return dataclasses.replace(res, counts=np.array(res.counts)[:len(res.counts) // 2])


def zero_half(res):
    """The second half of the batch answered 0, the shape kept."""
    counts = np.array(res.counts)
    counts[len(counts) // 2:] = 0
    return dataclasses.replace(res, counts=counts)


FAULTS = {"sound": lambda res: res, "answer_altered": alter_answers,
          "half_left_out": leave_out_half, "half_zeroed": zero_half}


class Session:
    """A plan session whose results pass through `fault`."""

    def __init__(self, sess, fault):
        self._sess, self._fault = sess, fault

    def submit(self, Q):
        return [self._fault(r) for r in self._sess.submit(Q)]

    def flush(self):
        return [self._fault(r) for r in self._sess.flush()]


class Faulty:
    """A built `JoinPlan` whose timed calls pass their results through
    `fault`."""

    def __init__(self, plan, fault):
        self._plan, self._fault = plan, fault

    def run(self, Q, eps):
        return self._fault(self._plan.run(Q, eps))

    def stream(self, batches, eps, depth):
        return self._plan.stream(batches, eps, depth=depth)

    def session(self, eps, depth):
        return Session(self._plan.session(eps, depth=depth), self._fault)


SEED = 2 ** 31 + 3


@functools.lru_cache(maxsize=None)
def sound_missed(workload: str) -> float:
    """`missed_pair_frac` of a sound run of the tiny cell."""
    with open(harness.os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        cell = tiny(harness.resolve_cell(harness.json.load(f), workload))
    return harness.run_cell(cell, SEED, 0.3, False)["checks"][
        "missed_pair_frac"]["value"]


def tiny_cell(bench, workload: str):
    cell = tiny(harness.resolve_cell(bench, workload))
    if "missed_pair_frac" in cell.checks:
        sound = sound_missed(workload)
        cell.checks["missed_pair_frac"]["limit"] = sound + (1 - sound) / 4
    return cell


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ["glove200-exact-s30k",
                                      "glove200-xjoin-s30k",
                                      "glove200-xjoin-stream256",
                                      "sift128-xjoin-s30k",
                                      "sift128-exact-s30k"])
def test_fault_fails_the_check(bench, workload, fault):
    cell = tiny_cell(bench, workload)
    out = harness.run_cell(cell, SEED, 0.3, False,
                           plan_hook=lambda p: Faulty(p, FAULTS[fault]))
    assert out["attempted"] > 0
    checks = out["checks"]
    assert list(out)[-1] == "checks"
    if fault == "sound":
        assert out["correct"] and out["failed"] == 0
        assert all(c["value"] <= c["limit"] for c in checks.values())
    else:
        assert not out["correct"] and out["failed"] > 0
        assert any(c["value"] > c["limit"] for c in checks.values())
