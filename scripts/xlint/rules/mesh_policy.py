"""mesh-policy rule (DESIGN.md §7): one mesh constructor.

Every mesh in the repo must be built through `launch/mesh.py::make_mesh`,
which pins each axis to `AxisType.Auto` so sharding propagation does not
depend on the default of the installed JAX.  This rule rejects,
everywhere EXCEPT that module:

  * `jax.sharding.Mesh(...)` / bare imported `Mesh(...)` constructor calls
  * `jax.make_mesh(...)` calls
  * any attribute access of `AxisType` (including `getattr` probing is
    left to mesh.py — nobody else should even reference the name)
  * an `axis_types=` keyword in any call
  * `from jax.sharding import Mesh / AxisType` imports

Type annotations (`m: jax.sharding.Mesh`) stay legal — only calls,
keywords, and `AxisType` references are policy violations.
"""
from __future__ import annotations

import ast

from xlint.core import LintFile, Rule, Violation

#: the one module allowed to touch the raw constructors
EXEMPT = ("src/repro/launch/mesh.py",)


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (`jax.sharding.Mesh`)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class MeshPolicyRule(Rule):
    """Flag mesh construction that bypasses `make_mesh` (DESIGN.md §7)."""

    id = "mesh-policy"
    design_ref = "§7"
    description = ("all mesh construction goes through "
                   "launch/mesh.py::make_mesh; never touch "
                   "jax.sharding.AxisType or axis_types= directly")
    targets = None              # repo-wide

    def select(self, lf: LintFile) -> bool:
        """Everywhere except the mesh module itself."""
        rel = lf.rel.replace("\\", "/")
        if any(rel.endswith(e) for e in EXEMPT):
            return False
        return super().select(lf)

    def check(self, lf: LintFile) -> list[Violation]:
        """Walk the AST for raw-constructor calls and AxisType refs."""
        out: list[Violation] = []
        for node in ast.walk(lf.tree):
            if isinstance(node, ast.Attribute) and node.attr == "AxisType":
                out.append(self.violation(
                    lf, node.lineno,
                    "jax.sharding.AxisType referenced directly — "
                    "launch/mesh.py::make_mesh owns the axis types"))
            elif isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name.endswith("sharding.Mesh") or name == "Mesh":
                    out.append(self.violation(
                        lf, node.lineno,
                        f"raw mesh constructor {name}(...) — build meshes "
                        "via launch/mesh.py::make_mesh"))
                elif name.endswith("jax.make_mesh"):
                    out.append(self.violation(
                        lf, node.lineno,
                        "jax.make_mesh(...) called directly — use "
                        "launch/mesh.py::make_mesh"))
                for kw in node.keywords:
                    if kw.arg == "axis_types":
                        out.append(self.violation(
                            lf, node.lineno,
                            "axis_types= passed directly — only "
                            "launch/mesh.py::make_mesh sets it"))
            elif isinstance(node, ast.ImportFrom):
                if node.module and node.module.startswith("jax.sharding"):
                    for alias in node.names:
                        if alias.name in ("Mesh", "AxisType"):
                            out.append(self.violation(
                                lf, node.lineno,
                                f"importing {alias.name} from jax.sharding "
                                "— construct meshes via "
                                "launch/mesh.py::make_mesh"))
        return out
