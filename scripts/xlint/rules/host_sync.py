"""host-sync rule (DESIGN.md §11/§12): no silent syncs in the hot path.

The streamed join pipeline's performance claim is that the exact and
device-probe routes perform exactly two per-batch host transfers — the
positive-count read and the result readback.  This rule keeps new code
from quietly adding a third: inside the HOT functions of
`core/engine.py` / `core/probe.py` (the three pipeline stages, the
stream/session drivers, and the placed-probe dispatchers, nested
closures included) it flags

  * `np.asarray(...)` / `int(...)` / `float(...)` applied to a
    device-resident value — recognized by the repo-wide `*dev` naming
    convention (`st.n_pos_dev`, `counts_dev`, `qdev`, ...)
  * `.item()` and `.block_until_ready()` anywhere in a hot function

unless the line (or the comment line above it) carries

    # xlint: allow-host-sync(<kind>: <reason>)

where `<kind>` must be a sync kind DECLARED in the same module by a
`_note_host_sync("<kind>")` / `_allowed_transfer("<kind>")` call — the
annotation is only valid adjacent to instrumentation, so the static
suppression and the runtime guard/instrumentation layers can never
drift apart.  A fixture file opts in with `# xlint: scope(host-sync)`,
which makes EVERY function hot.
"""
from __future__ import annotations

import ast
import re

from xlint.core import LintFile, Rule, Violation

#: device-resident values follow the `*dev` suffix convention
DEV_NAME_RE = re.compile(r".*dev$")

#: hot-path functions per target file (qualnames)
HOT_FUNCTIONS = {
    "src/repro/core/engine.py": {
        "JoinEngine._stage_filter", "JoinEngine._stage_probe",
        "JoinEngine._commit_verify", "JoinEngine._dispatch_verify",
        "JoinEngine.stream",
        "PendingJoin.result", "StreamSession.submit", "StreamSession.flush",
        "StreamSession._commit_probed", "StreamSession._advance_staged",
    },
    "src/repro/core/probe.py": {
        "PlacedProbe.probe", "PlacedProbe.verify",
    },
    # the push-interface session (api.py) and the gateway's per-request
    # path sit directly on the stream pipeline — same two-syncs budget
    "src/repro/core/api.py": {
        "PlanSession.submit", "PlanSession.flush",
    },
    # planner measurement programs: one sanctioned histogram readback per
    # auto() (annotated allow-host-sync), nothing on the per-batch path
    "src/repro/core/planner.py": {
        "measure_skew", "measure_workload",
    },
    "src/repro/serve/gateway.py": {
        "Gateway.submit", "Gateway._pump", "Gateway._scatter",
        "Gateway.flush",
    },
}


def _mentions_dev_value(node: ast.AST) -> bool:
    """Whether any identifier under `node` names a device value."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and DEV_NAME_RE.match(sub.id):
            return True
        if isinstance(sub, ast.Attribute) and DEV_NAME_RE.match(sub.attr):
            return True
    return False


def _declared_kinds(tree: ast.AST) -> set[str]:
    """Sync kinds declared by `_note_host_sync("...")` /
    `_allowed_transfer("...")` calls in this module."""
    kinds: set[str] = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in ("_note_host_sync", "_allowed_transfer")
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            kinds.add(node.args[0].value)
    return kinds


def _sync_calls(fn: ast.AST):
    """(node, label) for every host-sync-shaped call under `fn`."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr == "block_until_ready":
                yield node, ".block_until_ready()"
            elif f.attr == "item":
                yield node, ".item()"
            elif (f.attr == "asarray" and isinstance(f.value, ast.Name)
                    and f.value.id in ("np", "numpy")
                    and _mentions_dev_value(node)):
                yield node, "np.asarray() on a device value"
        elif isinstance(f, ast.Name) and f.id in ("int", "float"):
            if node.args and _mentions_dev_value(node.args[0]):
                yield node, f"{f.id}() on a device value"


class HostSyncRule(Rule):
    """Flag unannotated host syncs in the pipeline hot path (§11)."""

    id = "host-sync"
    design_ref = "§11"
    description = ("hot-path host syncs (np.asarray/int/float on *dev "
                   "values, .item, block_until_ready) must carry "
                   "allow-host-sync(<kind>: <reason>) with an "
                   "instrumented kind")
    targets = tuple(HOT_FUNCTIONS)

    def _hot_functions(self, lf: LintFile) -> list[ast.AST]:
        rel = lf.rel.replace("\\", "/")
        hot = None
        for path, names in HOT_FUNCTIONS.items():
            if rel.endswith(path):
                hot = names
        out = []

        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    if hot is None or qual in hot:
                        out.append(child)
                    # nested defs of a hot fn are covered by ast.walk;
                    # only class bodies need descending here
                elif isinstance(child, ast.ClassDef):
                    visit(child, prefix=f"{prefix}{child.name}.")

        visit(lf.tree, "")      # hot=None (scoped fixture): all functions
        return out

    def check(self, lf: LintFile) -> list[Violation]:
        """Flag sync-shaped calls in hot functions, validating the
        `allow-host-sync(<kind>: <reason>)` annotations against the
        module's declared instrumentation kinds."""
        declared = _declared_kinds(lf.tree)
        out: list[Violation] = []
        seen: set[int] = set()
        for fn in self._hot_functions(lf):
            for node, label in _sync_calls(fn):
                if node.lineno in seen:
                    continue
                seen.add(node.lineno)
                ann = lf.allow_at(node.lineno, self.id)
                if ann is None:
                    out.append(self.violation(
                        lf, node.lineno,
                        f"{label} in hot path without an "
                        "allow-host-sync(<kind>: <reason>) annotation"))
                    continue
                kind, _, reason = ann.arg.partition(":")
                kind, reason = kind.strip(), reason.strip()
                if kind not in declared or not reason:
                    out.append(self.violation(
                        lf, node.lineno,
                        f"allow-host-sync kind {kind!r} is not a "
                        "_note_host_sync/_allowed_transfer kind declared "
                        "in this module (or the reason is empty)",
                        suppressible=False))
                else:
                    lf.mark_used(ann)
        return out
