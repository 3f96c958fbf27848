"""Repo-invariant linter — the ROADMAP's standing constraints, machine-checked.

AST-based (stdlib only — the CI lint job installs neither jax nor numpy, so
this module must import cleanly without them).  Exposed as
``scripts/lint_invariants.py`` and a blocking CI step.

Rules:

* **compat-jit / compat-shard-map / compat-mesh / compat-cost-analysis** —
  the mesh/sharding-sensitive JAX APIs (``jax.jit``, ``jax.shard_map``,
  ``Mesh(`` construction, ``.cost_analysis()``) must route through
  ``repro/compat.py``, which owns the axis-type and manual-axis decisions.
  Scope: ``src/repro``, ``benchmarks/``, ``scripts/`` and ``examples/`` —
  the quickstarts are the repo's public face and must model the supported
  API, so they get the full rule set (tests deliberately exercise raw JAX —
  e.g. ``tests/test_compat.py`` — and are exempt).
* **hypothesis-shim** — ``hypothesis`` may only be imported by
  ``tests/_prop.py`` (the optional-dependency shim); everything else goes
  through the shim so the hermetic CI lane still collects.
* **paramdef-scale** — every ``ParamDef`` constructed with a literal shape of
  rank >= 3 must pass an explicit ``scale=`` (or a zeros/ones init): the
  fan-in heuristic reads ``shape[-2]``, which is wrong for stacked/expert
  projections (the zamba2 PR 1 bug).
* **calibration-constant** — cost/memory-model coefficients must be read
  through ``CostEnv``/``Calibration`` (``repro/core/calibrate.py``), not
  introduced as fresh module-level numeric constants in
  ``core/cost_model.py`` / ``core/memory_model.py``.  Dtype/byte-layout
  facts (``GRAD_BYTES`` etc.) are allowlisted; aliases to ``calibrate``
  attributes are fine (not literals).
* **obs-print** — no bare ``print(`` in ``src/repro/runtime/``: runtime
  telemetry routes through ``repro.obs`` (sink events / ``format_live_line``)
  so it stays machine-readable; stray prints vanish from run logs.
* **serve-config** — no direct ``ServingEngine(`` construction outside
  ``repro/serving`` (and the class's own module): the supported serving
  surface is the validated ``ServeConfig`` + ``repro.serving.build`` facade;
  step-level access goes through ``repro.serving.step_engine``.
* **family-read** — ``src/`` reads a config's ``family`` attribute only in
  ``repro/configs/arch.py``; the rest asks ``arch_of(cfg)`` or the layer
  kinds of ``layer_slots(cfg)``, so a new architecture is one row there.
* **galv-catalog** — repo-level (not per-file): every ``GALV###`` code
  referenced by the verifier/auditor sources (``plan_check.py``,
  ``hlo_audit.py``, ``jaxpr_audit.py``) must appear in the ``plan_check``
  module-docstring table, in ``README.md``, and in
  ``tests/test_plan_verifier.py`` (where each code keeps a failing/passing
  twin).  A new diagnostic code can no longer ship undocumented or untested.
  Skipped for trees without ``src/repro/analysis/plan_check.py`` (lint-test
  fixtures).
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import pathlib
import re
from typing import Iterable, Optional

SKIP_DIRS = {".git", "__pycache__", ".claude", "results", ".github",
             "node_modules", ".venv"}

#: rules enforcing compat.py routing (not applied to tests/ or compat.py)
COMPAT_RULES = ("compat-jit", "compat-shard-map", "compat-mesh",
                "compat-cost-analysis")

#: verifier/auditor sources whose GALV### references define the catalog
GALV_SOURCE_FILES = ("src/repro/analysis/plan_check.py",
                     "src/repro/analysis/hlo_audit.py",
                     "src/repro/analysis/jaxpr_audit.py")
#: surfaces every referenced code must appear on (besides the docstring)
GALV_SURFACE_FILES = ("README.md", "tests/test_plan_verifier.py")
_GALV_CODE_RE = re.compile(r"GALV\d{3}")

#: files whose module-level numeric constants are calibration-scoped
CALIBRATION_SCOPED_FILES = {"src/repro/core/cost_model.py",
                            "src/repro/core/memory_model.py"}
#: dtype/byte-layout facts — legitimately fixed, never fitted
CALIBRATION_CONST_ALLOW = {"GRAD_BYTES", "PIPELINE_BOUNDARY_BYTES_PER_ELEM",
                           "MASTER_BYTES", "OPT_BYTES"}


@dataclasses.dataclass(frozen=True)
class LintViolation:
    path: str                   # repo-root-relative, posix separators
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


def _rules_for(rel: pathlib.PurePosixPath) -> frozenset[str]:
    """Which rules apply to one repo-relative file."""
    parts = rel.parts
    family = ({"family-read"} if parts[:1] == ("src",)
              and str(rel) != "src/repro/configs/arch.py" else set())
    if str(rel) == "src/repro/compat.py":
        return frozenset({"hypothesis-shim", "paramdef-scale"} | family)
    if parts and parts[0] == "tests":
        if str(rel) == "tests/_prop.py":
            return frozenset()
        return frozenset({"hypothesis-shim"})
    rules = frozenset(COMPAT_RULES) | {"hypothesis-shim", "paramdef-scale",
                                       "serve-config"} | family
    if str(rel) in CALIBRATION_SCOPED_FILES:
        rules = rules | {"calibration-constant"}
    if parts[:3] == ("src", "repro", "runtime"):
        rules = rules | {"obs-print"}
    if (parts[:3] == ("src", "repro", "serving")
            or str(rel) == "src/repro/runtime/serve.py"):
        rules = rules - {"serve-config"}
    return rules


class _Visitor(ast.NodeVisitor):
    def __init__(self, rel: str, rules: frozenset[str]):
        self.rel = rel
        self.rules = rules
        self.violations: list[LintViolation] = []
        self.jax_aliases: set[str] = set()      # names bound to the jax module

    def _flag(self, node: ast.AST, rule: str, message: str) -> None:
        if rule in self.rules:
            self.violations.append(LintViolation(
                self.rel, getattr(node, "lineno", 0),
                getattr(node, "col_offset", 0), rule, message))

    # ---------------------------------------------------------- module body
    def visit_Module(self, node: ast.Module) -> None:
        if "calibration-constant" in self.rules:
            for stmt in node.body:
                self._check_calibration_const(stmt)
        self.generic_visit(node)

    def _check_calibration_const(self, stmt: ast.stmt) -> None:
        """Flag ``UPPER_NAME = <numeric literal>`` at module level in the
        cost/memory models — tunable coefficients belong in
        ``repro.core.calibrate`` where measurement can fit them."""
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            return
        lit = value
        if (isinstance(lit, ast.UnaryOp)
                and isinstance(lit.op, (ast.USub, ast.UAdd))):
            lit = lit.operand
        if not (isinstance(lit, ast.Constant)
                and isinstance(lit.value, (int, float))
                and not isinstance(lit.value, bool)):
            return
        for t in targets:
            if (isinstance(t, ast.Name) and t.id == t.id.upper()
                    and t.id not in CALIBRATION_CONST_ALLOW):
                self._flag(stmt, "calibration-constant",
                           f"module-level coefficient {t.id} = {lit.value!r} "
                           "— route it through CostEnv/Calibration "
                           "(repro.core.calibrate) so measurement can fit "
                           "it, or allowlist it if it is a dtype/byte-"
                           "layout fact")

    # ---------------------------------------------------------- imports
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            if alias.name == "jax":
                self.jax_aliases.add(alias.asname or "jax")
            if (alias.name == "hypothesis"
                    or alias.name.startswith("hypothesis.")):
                self._flag(node, "hypothesis-shim",
                           "import hypothesis via tests/_prop.py (the "
                           "optional-dependency shim), not directly")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        if mod == "hypothesis" or mod.startswith("hypothesis."):
            self._flag(node, "hypothesis-shim",
                       "import hypothesis via tests/_prop.py (the optional-"
                       "dependency shim), not directly")
        if mod == "jax":
            for alias in node.names:
                if alias.name == "jit":
                    self._flag(node, "compat-jit",
                               "import jit via repro.compat (compat.jit), "
                               "not from jax directly")
                if alias.name == "shard_map":
                    self._flag(node, "compat-shard-map",
                               "import shard_map via repro.compat, not from "
                               "jax directly")
        if mod == "jax.experimental.shard_map":
            self._flag(node, "compat-shard-map",
                       "use repro.compat.shard_map, not the "
                       "experimental module")
        self.generic_visit(node)

    # ---------------------------------------------------------- uses
    def visit_Attribute(self, node: ast.Attribute) -> None:
        if node.attr == "family" and isinstance(node.ctx, ast.Load):
            self._flag(node, "family-read",
                       "family attribute read outside repro/configs/arch.py — ask "
                       "arch_of(cfg) or the layer kinds of layer_slots(cfg)")
        if (isinstance(node.value, ast.Name)
                and node.value.id in self.jax_aliases):
            if node.attr == "jit":
                self._flag(node, "compat-jit",
                           "jax.jit bypasses the compat shim — use "
                           "repro.compat.jit")
            elif node.attr == "shard_map":
                self._flag(node, "compat-shard-map",
                           "jax.shard_map bypasses the compat shim — use "
                           "repro.compat.shard_map")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        # Mesh(...) construction anywhere outside compat.py
        name = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None)
        if name == "Mesh":
            self._flag(node, "compat-mesh",
                       "construct meshes via repro.compat.make_mesh, not "
                       "Mesh(...) directly")
        # <expr>.cost_analysis() — version-sensitive return shape
        if (isinstance(fn, ast.Attribute) and fn.attr == "cost_analysis"
                and not (isinstance(fn.value, ast.Name)
                         and fn.value.id == "compat")
                and not (isinstance(fn.value, ast.Attribute)
                         and fn.value.attr == "compat")):
            self._flag(node, "compat-cost-analysis",
                       "use repro.compat.cost_analysis(obj), which "
                       "returns a flat dict")
        if name == "ParamDef":
            self._check_paramdef(node)
        if name == "ServingEngine":
            self._flag(node, "serve-config",
                       "direct ServingEngine(...) construction — the "
                       "supported entry points are repro.serving.build "
                       "(ServeConfig facade) and repro.serving.step_engine")
        if isinstance(fn, ast.Name) and fn.id == "print":
            self._flag(node, "obs-print",
                       "bare print() in the runtime layer — emit through "
                       "repro.obs (RunSink event or format_live_line) so "
                       "telemetry stays machine-readable")
        self.generic_visit(node)

    def _check_paramdef(self, node: ast.Call) -> None:
        shape: Optional[ast.expr] = None
        if node.args:
            shape = node.args[0]
        kw = {k.arg: k.value for k in node.keywords if k.arg is not None}
        shape = kw.get("shape", shape)
        if not isinstance(shape, ast.Tuple) or len(shape.elts) < 3:
            return                      # non-literal or < 3-D: heuristic is fine
        init = kw.get("init")
        if (isinstance(init, ast.Constant)
                and init.value in ("zeros", "ones")):
            return
        if "scale" not in kw:
            self._flag(node, "paramdef-scale",
                       f"{len(shape.elts)}-D ParamDef without explicit "
                       "scale= — the fan-in heuristic reads shape[-2], which "
                       "is wrong for stacked projections (zamba2 rule)")


def lint_source(source: str, rel: str,
                rules: Optional[frozenset[str]] = None) -> list[LintViolation]:
    """Lint one file's source text (``rel`` is its repo-relative path)."""
    if rules is None:
        rules = _rules_for(pathlib.PurePosixPath(rel))
    if not rules:
        return []
    try:
        tree = ast.parse(source, filename=rel)
    except SyntaxError as e:
        return [LintViolation(rel, e.lineno or 0, e.offset or 0,
                              "syntax-error", str(e.msg))]
    v = _Visitor(rel, rules)
    v.visit(tree)
    return sorted(v.violations, key=lambda x: (x.line, x.col))


def iter_py_files(root: pathlib.Path) -> Iterable[pathlib.Path]:
    for path in sorted(root.rglob("*.py")):
        if any(part in SKIP_DIRS for part in path.relative_to(root).parts):
            continue
        yield path


def lint_galv_catalog(root: pathlib.Path) -> list[LintViolation]:
    """Repo-level galv-catalog rule: every GALV### code the verifier or the
    compiled-artifact auditor references must be documented in the
    ``plan_check`` module docstring, listed in ``README.md`` and exercised
    (failing/passing twin) in ``tests/test_plan_verifier.py``.  Skipped for
    trees without the verifier (the lint tests' tmp fixtures)."""
    anchor = root / GALV_SOURCE_FILES[0]
    if not anchor.is_file():
        return []

    def text_of(rel: str) -> str:
        p = root / rel
        try:
            return p.read_text(encoding="utf-8") if p.is_file() else ""
        except (OSError, UnicodeDecodeError):
            return ""

    referenced: dict[str, str] = {}       # code -> first referencing source
    for rel in GALV_SOURCE_FILES:
        for m in _GALV_CODE_RE.finditer(text_of(rel)):
            referenced.setdefault(m.group(0), rel)

    try:
        docstring = ast.get_docstring(
            ast.parse(anchor.read_text(encoding="utf-8"))) or ""
    except (OSError, SyntaxError):
        docstring = ""
    surfaces = [(GALV_SOURCE_FILES[0] + " (module docstring table)",
                 docstring)]
    surfaces += [(rel, text_of(rel)) for rel in GALV_SURFACE_FILES]

    out: list[LintViolation] = []
    for code in sorted(referenced):
        # the docstring table lists bare 3-digit rows ("090   comm-mismatch")
        bare_row = re.compile(rf"^{code[4:]}\s+\S", re.MULTILINE)
        for surface, text in surfaces:
            if code not in text and not (
                    "docstring" in surface and bare_row.search(text)):
                out.append(LintViolation(
                    surface.split(" ")[0], 0, 0, "galv-catalog",
                    f"{code} (referenced by {referenced[code]}) is missing "
                    f"from {surface} — every diagnostic code ships with its "
                    "docstring-table row, README row and verifier-test twin"))
    return out


def lint_paths(root: pathlib.Path) -> list[LintViolation]:
    out: list[LintViolation] = []
    for path in iter_py_files(root):
        rel = path.relative_to(root).as_posix()
        try:
            source = path.read_text(encoding="utf-8")
        except (OSError, UnicodeDecodeError) as e:
            out.append(LintViolation(rel, 0, 0, "unreadable", str(e)))
            continue
        out.extend(lint_source(source, rel))
    out.extend(lint_galv_catalog(root))
    return out


def main(argv: Optional[list[str]] = None,
         default_root: str = ".") -> int:
    ap = argparse.ArgumentParser(
        description="Enforce the repo's standing invariants (compat-shim "
                    "routing, hypothesis shim, explicit ParamDef scales, "
                    "calibration-scoped cost-model coefficients).")
    ap.add_argument("--root", default=default_root,
                    help="repository root to lint (default: %(default)s)")
    args = ap.parse_args(argv)
    root = pathlib.Path(args.root).resolve()
    if not root.is_dir():
        print(f"lint_invariants: not a directory: {root}")
        return 2
    violations = lint_paths(root)
    for v in violations:
        print(v)
    n_files = sum(1 for _ in iter_py_files(root))
    status = "FAIL" if violations else "OK"
    print(f"lint_invariants: {status} — {len(violations)} violation(s) "
          f"in {n_files} file(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
