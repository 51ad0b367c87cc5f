"""Unified-kwargs contract: the same knob has the same name — and is
keyword-only — everywhere on the public analysis surface.

The redesign PR unified ``seed`` / ``deadline_s`` / ``backend`` /
``max_batch`` across ``plan.*``, the service ``submit*``/``query*`` family
and the samplers; this test pins that contract so a future method can't
drift (e.g. re-introduce a positional ``backend`` or rename ``seed`` to
``rng``).  It also pins the layering: ``repro.core`` and ``repro.sweep``
import nothing from ``repro.analysis``."""

import ast
import inspect
import pathlib

from repro.analysis.optimize import run_optimize
from repro.analysis.plan import CompiledWorkflow
from repro.analysis.serve import AnalysisService, OnlineReanalysis
from repro.analysis.uncertainty import run_mc, sample_spec

UNIFIED = ("seed", "deadline_s", "backend", "max_batch")

#: callable -> unified kwargs it must expose (all keyword-only)
SURFACE = {
    CompiledWorkflow.sweep: ("backend",),
    CompiledWorkflow.mc: ("seed", "backend"),
    CompiledWorkflow.optimize: ("seed", "deadline_s"),
    run_optimize: ("seed", "deadline_s"),
    run_mc: ("seed", "backend"),
    sample_spec: ("seed",),
    AnalysisService.__init__: ("backend", "max_batch"),
    AnalysisService.submit: ("deadline_s",),
    AnalysisService.submit_mc: ("seed", "deadline_s", "max_batch"),
    AnalysisService.query_mc: ("seed", "deadline_s", "max_batch"),
    AnalysisService.submit_optimize: ("seed", "deadline_s"),
    AnalysisService.query_optimize: ("seed", "deadline_s"),
    OnlineReanalysis.__init__: ("backend",),
}


def test_unified_kwargs_present_and_keyword_only():
    for fn, required in SURFACE.items():
        params = inspect.signature(fn).parameters
        for kw in required:
            assert kw in params, f"{fn.__qualname__} lost kwarg {kw!r}"
            assert params[kw].kind is inspect.Parameter.KEYWORD_ONLY, \
                f"{fn.__qualname__}({kw}=...) must be keyword-only"


def test_no_unified_kwarg_is_ever_positional():
    """Even where a unified knob is optional, it must never be acceptable
    positionally."""
    for fn in SURFACE:
        for name, p in inspect.signature(fn).parameters.items():
            if name in UNIFIED:
                assert p.kind is inspect.Parameter.KEYWORD_ONLY, \
                    f"{fn.__qualname__}: {name} must be keyword-only"


def test_unified_defaults_agree():
    """Shared knobs default the same way everywhere they appear (one mental
    model: seed=0 unless the API treats None as 'inherit')."""
    defaults = {}
    for fn in SURFACE:
        for name, p in inspect.signature(fn).parameters.items():
            if name in ("deadline_s", "backend"):
                defaults.setdefault(name, set()).add(p.default)
    assert defaults["deadline_s"] == {None}
    assert defaults["backend"] == {"auto"}


# ------------------------------------------------------------ layering ----
SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

#: the one deliberate upward call: ``Workflow.compile()`` is the documented
#: front door and builds the analysis layer's plan when it is called
UPWARD_ENTRIES = {("core/workflow.py", "Workflow.compile")}


def _imported_modules(node: ast.Import | ast.ImportFrom,
                      package: list[str]) -> list[str]:
    if isinstance(node, ast.Import):
        return [a.name for a in node.names]
    base = ".".join(package[:len(package) - node.level + 1]
                    if node.level else [])
    mod = ".".join(p for p in (base, node.module) if p)
    return [mod] + [f"{mod}.{a.name}" for a in node.names]


def _analysis_imports(path: pathlib.Path) -> list[str]:
    rel = path.relative_to(SRC).as_posix()
    package = ["repro", *rel.split("/")[:-1]]
    hits: list[str] = []

    def walk(nodes: list[ast.AST], scope: list[str]) -> None:
        for node in nodes:
            if (isinstance(node, ast.If)
                    and ast.unparse(node.test).endswith("TYPE_CHECKING")):
                walk(node.orelse, scope)  # the guarded body never runs
                continue
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                for mod in _imported_modules(node, package):
                    upward = (mod == "repro.analysis"
                              or mod.startswith("repro.analysis."))
                    if upward and (rel, ".".join(scope)) not in UPWARD_ENTRIES:
                        hits.append(f"{rel}:{node.lineno} imports {mod}")
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                walk(node.body, scope + [node.name])
            else:
                walk(list(ast.iter_child_nodes(node)), scope)

    walk(ast.parse(path.read_text()).body, [])
    return hits


def test_lower_layers_do_not_import_analysis():
    """``repro.core`` and ``repro.sweep`` sit below ``repro.analysis``:
    importing them must not load the analysis layer."""
    hits = [hit for layer in ("core", "sweep")
            for path in sorted((SRC / layer).rglob("*.py"))
            for hit in _analysis_imports(path)]
    assert hits == []
