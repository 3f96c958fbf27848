"""Repo-invariant linter: the repo itself lints clean, each rule fires on a
minimal fixture (and not on its compliant twin), and the CLI wrapper exits
nonzero on a fixture tree containing a direct ``jax.jit``."""
import pathlib
import subprocess
import sys
import textwrap

from repro.analysis.lint_repo import lint_paths, lint_source

REPO = pathlib.Path(__file__).resolve().parents[1]


def _codes(source, rel="src/repro/somewhere.py"):
    return [v.rule for v in lint_source(textwrap.dedent(source), rel)]


def test_repo_lints_clean():
    violations = lint_paths(REPO)
    assert not violations, "\n".join(map(str, violations))


# --------------------------------------------------------------- compat-*

def test_direct_jit_flagged_and_compat_jit_clean():
    assert _codes("import jax\nf = jax.jit(lambda x: x)\n") == ["compat-jit"]
    assert _codes("import jax as j\nf = j.jit(g)\n") == ["compat-jit"]
    assert _codes("from jax import jit\n") == ["compat-jit"]
    assert not _codes("from repro import compat\nf = compat.jit(g)\n")


def test_family_read_flagged_outside_arch_module():
    src = "def f(cfg):\n    return cfg.family == 'dense'\n"
    assert _codes(src) == ["family-read"]
    assert _codes("x = f'{cfg.family!r}'\n") == ["family-read"]
    assert not _codes(src, rel="src/repro/configs/arch.py")
    assert not _codes("from repro.configs.arch import arch_of\n"
                      "ok = arch_of(cfg).cp\n")
    for rel in ("tests/test_x.py", "benchmarks/x.py", "examples/x.py"):
        assert "family-read" not in _codes(src, rel=rel)


def test_shard_map_and_mesh_rules():
    assert "compat-shard-map" in _codes(
        "import jax\ns = jax.shard_map(f, mesh=m)\n")
    assert "compat-shard-map" in _codes(
        "from jax.experimental.shard_map import shard_map\n")
    assert "compat-mesh" in _codes("m = Mesh(devs, ('data',))\n")
    assert not _codes("m = compat.make_mesh((4,), ('data',))\n")


def test_cost_analysis_rule():
    assert "compat-cost-analysis" in _codes("stats = compiled.cost_analysis()\n")
    assert not _codes("from repro import compat\nca = compat.cost_analysis(c)\n")


def test_compat_module_itself_is_exempt():
    assert not _codes("import jax\nf = jax.jit(g)\nm = Mesh(d, a)\n",
                      rel="src/repro/compat.py")


def test_tests_exempt_from_compat_rules_but_not_hypothesis():
    assert not _codes("import jax\nf = jax.jit(g)\n",
                      rel="tests/test_thing.py")
    assert _codes("import hypothesis\n", rel="tests/test_thing.py") \
        == ["hypothesis-shim"]


# ---------------------------------------------------------- hypothesis-shim

def test_hypothesis_only_via_prop_shim():
    assert _codes("from hypothesis import given\n") == ["hypothesis-shim"]
    assert _codes("from hypothesis.strategies import integers\n") \
        == ["hypothesis-shim"]
    assert not _codes("from hypothesis import given\n", rel="tests/_prop.py")
    assert not _codes("from tests._prop import given, st\n",
                      rel="tests/test_thing.py")


# -------------------------------------------------------------- serve-config

def test_direct_serving_engine_construction_flagged():
    bad = "from repro.runtime.serve import ServingEngine\n" \
          "eng = ServingEngine(model, plan, mesh)\n"
    assert "serve-config" in _codes(bad)
    ok = "from repro import serving\n" \
         "eng = serving.step_engine(model, plan, mesh)\n"
    assert not _codes(ok)


def test_serving_package_and_runtime_serve_exempt_from_serve_config():
    src = "eng = ServingEngine(model, plan, mesh)\n"
    assert not _codes(src, rel="src/repro/serving/__init__.py")
    assert not _codes(src, rel="src/repro/runtime/serve.py")
    assert not _codes(src, rel="tests/test_thing.py")
    # but other runtime modules are NOT exempt
    assert "serve-config" in _codes(src, rel="src/repro/runtime/other.py")


# ------------------------------------------------------------ paramdef-scale

def test_paramdef_3d_needs_explicit_scale():
    bad = 'd = ParamDef((e, d, f), ("experts", "embed", "ff"))\n'
    assert _codes(bad) == ["paramdef-scale"]
    ok = ('d = ParamDef((e, d, f), ("experts", "embed", "ff"), '
          'scale=1.0 / math.sqrt(d))\n')
    assert not _codes(ok)
    # 2-D defs keep the fan-in heuristic; zeros/ones need no scale
    assert not _codes('d = ParamDef((d, f), ("embed", "ff"))\n')
    assert not _codes('d = ParamDef((e, d, f), ("a", "b", "c"), init="zeros")\n')


# ------------------------------------------------------------------- CLI

def test_cli_exits_nonzero_on_fixture_with_direct_jit(tmp_path):
    pkg = tmp_path / "src"
    pkg.mkdir()
    (pkg / "offender.py").write_text(
        "import jax\n\nstep = jax.jit(lambda x: x + 1)\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_invariants.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True)
    assert proc.returncode == 1
    assert "compat-jit" in proc.stdout and "offender.py" in proc.stdout


def test_cli_exits_zero_on_clean_fixture(tmp_path):
    (tmp_path / "fine.py").write_text(
        "from repro import compat\n\nstep = compat.jit(lambda x: x + 1)\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_invariants.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


def test_cli_on_repo_root_is_clean():
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "lint_invariants.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_lint_repo_is_stdlib_only():
    """The CI lint job installs nothing but ruff — the linter must import
    without jax/numpy on the path."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys;"
         "sys.modules['jax'] = None; sys.modules['numpy'] = None;"
         "sys.path.insert(0, 'src');"
         "from repro.analysis import lint_repo;"
         "print(len(lint_repo.lint_source('import jax\\nf=jax.jit(g)', "
         "'src/x.py')))"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "1"


# ------------------------------------------------------------------ obs-print

def test_bare_print_flagged_in_runtime_layer_only():
    bad = "print('step', step)\n"
    assert _codes(bad, rel="src/repro/runtime/train.py") == ["obs-print"]
    assert _codes(bad, rel="src/repro/runtime/serve.py") == ["obs-print"]
    # the launch drivers own the human-facing console line; everything else
    # outside src/repro/runtime/ is out of scope too
    assert _codes(bad, rel="src/repro/launch/train.py") == []
    assert _codes(bad, rel="src/repro/obs/sink.py") == []
    assert _codes(bad, rel="tests/test_x.py") == []
    # sink emission and attribute calls are the sanctioned paths
    assert _codes("sink.emit('step', loss=loss)\n",
                  rel="src/repro/runtime/train.py") == []
    assert _codes("logging.info('x')\n",
                  rel="src/repro/runtime/train.py") == []


# -------------------------------------------------------- calibration-constant

def test_fresh_cost_model_constant_flagged():
    bad = "NEW_FUDGE_FACTOR = 1.7\n"
    assert _codes(bad, rel="src/repro/core/cost_model.py") == \
        ["calibration-constant"]
    assert _codes(bad, rel="src/repro/core/memory_model.py") == \
        ["calibration-constant"]
    # negative literals and annotated assignments are still literals
    assert _codes("K: float = -0.5\n",
                  rel="src/repro/core/cost_model.py") == \
        ["calibration-constant"]


def test_calibration_constant_scope_and_allowlist():
    bad = "NEW_FUDGE_FACTOR = 1.7\n"
    # the rule is scoped to the cost/memory models only
    assert _codes(bad, rel="src/repro/core/search.py") == []
    assert _codes(bad, rel="tests/test_x.py") == []
    # dtype/byte-layout facts are allowlisted
    assert _codes("GRAD_BYTES = 4.0\n",
                  rel="src/repro/core/cost_model.py") == []
    assert _codes("MASTER_BYTES = 4.0\nOPT_BYTES = 8.0\n",
                  rel="src/repro/core/memory_model.py") == []
    # aliases to calibrate attributes are bindings, not fresh literals
    assert _codes(
        "from repro.core import calibrate\n"
        "BWD_FLOPS_FACTOR = calibrate.ANALYTIC_BWD_FLOPS_FACTOR\n",
        rel="src/repro/core/cost_model.py") == []
    # lowercase names and non-module-level literals are out of scope
    assert _codes("eps = 1e-9\n", rel="src/repro/core/cost_model.py") == []
    assert _codes("def f():\n    SCALE = 2.0\n    return SCALE\n",
                  rel="src/repro/core/cost_model.py") == []


# -------------------------------------------------------------- examples scope

def test_examples_get_full_default_rules_and_are_walked():
    """examples/ is the repo's public face: it is inside the lint walk and
    gets the complete default rule set (compat routing included)."""
    from repro.analysis.lint_repo import COMPAT_RULES, _rules_for, iter_py_files

    rules = _rules_for(pathlib.PurePosixPath("examples/quickstart.py"))
    assert set(COMPAT_RULES) <= rules
    assert "serve-config" in rules and "hypothesis-shim" in rules
    assert _codes("import jax\nf = jax.jit(g)\n",
                  rel="examples/quickstart.py") == ["compat-jit"]

    walked = {p.relative_to(REPO).as_posix() for p in iter_py_files(REPO)}
    assert {"examples/quickstart.py", "examples/search_strategies.py",
            "examples/serve_batched.py", "examples/train_100m.py"} <= walked


# -------------------------------------------------------------- galv-catalog

def _galv_tree(tmp_path, *, docstring_row=True, readme_row=True,
               test_twin=True):
    """Minimal tree for the repo-level galv-catalog rule: a plan_check.py
    referencing GALV090 plus the three documentation surfaces."""
    anchor = tmp_path / "src" / "repro" / "analysis"
    anchor.mkdir(parents=True)
    doc = ('"""Verifier.\n\ncode  meaning\n090   comm-mismatch\n"""\n'
           if docstring_row else '"""Verifier."""\n')
    (anchor / "plan_check.py").write_text(doc + 'CODE = "GALV090"\n')
    (tmp_path / "README.md").write_text(
        "| GALV090 | comm-mismatch |\n" if readme_row else "nothing here\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_plan_verifier.py").write_text(
        'def test_galv090_pair():\n    assert "GALV090"\n'
        if test_twin else "pass\n")
    return tmp_path


def test_galv_catalog_clean_on_complete_fixture(tmp_path):
    from repro.analysis.lint_repo import lint_galv_catalog

    root = _galv_tree(tmp_path)
    assert lint_galv_catalog(root) == []
    # and through the full walk (integration with lint_paths)
    assert [v for v in lint_paths(root) if v.rule == "galv-catalog"] == []


def test_galv_catalog_flags_each_missing_surface(tmp_path):
    from repro.analysis.lint_repo import lint_galv_catalog

    no_readme = lint_galv_catalog(_galv_tree(tmp_path / "a", readme_row=False))
    assert [v.rule for v in no_readme] == ["galv-catalog"]
    assert no_readme[0].path == "README.md"
    assert "GALV090" in no_readme[0].message

    no_doc = lint_galv_catalog(
        _galv_tree(tmp_path / "b", docstring_row=False))
    assert [v.rule for v in no_doc] == ["galv-catalog"]
    assert "docstring" in no_doc[0].message

    no_twin = lint_galv_catalog(_galv_tree(tmp_path / "c", test_twin=False))
    assert [v.rule for v in no_twin] == ["galv-catalog"]
    assert no_twin[0].path == "tests/test_plan_verifier.py"


def test_galv_catalog_accepts_bare_docstring_rows_only_in_docstring(tmp_path):
    """The docstring table lists bare 3-digit rows; a bare "090" row in
    README or the tests does NOT satisfy those surfaces."""
    from repro.analysis.lint_repo import lint_galv_catalog

    root = _galv_tree(tmp_path, readme_row=False)
    (root / "README.md").write_text("090   comm-mismatch\n")
    out = lint_galv_catalog(root)
    assert [v.path for v in out] == ["README.md"]


def test_galv_catalog_skipped_without_verifier(tmp_path):
    """Trees without src/repro/analysis/plan_check.py (the CLI fixture
    trees above) never trip the repo-level rule."""
    from repro.analysis.lint_repo import lint_galv_catalog

    (tmp_path / "fine.py").write_text("x = 1\n")
    assert lint_galv_catalog(tmp_path) == []
    assert lint_paths(tmp_path) == []
