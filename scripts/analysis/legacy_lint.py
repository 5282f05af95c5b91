"""The scripts/lint.py rule families, absorbed behind the unified
driver (scripts/analyze.py); `scripts/lint.py` is now a thin wrapper
over this module so existing invocations (check.sh history, pre-commit
hooks, tests/test_audit.py's subprocess tests) keep working.

Rules (docs/static-analysis.md has the full catalog):

  F401 unused import          E722 bare except          B006 mutable default
  E711 ==/!= None             F811 top-level redef      W291 trailing ws
  E501 long line              TAB  tab indent           E999 syntax error
  M001 metric label outside the bounded-cardinality allowlist
  M002 docs-vs-registry metric drift (default-path runs only)
  M003 host work inside a `# hotpath:` fenced device region (ops/*.py)

M003 remains as the narrow lexical fence check; rule A005 (rules_jit)
is its call-graph-reach superset and covers unfenced helpers.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from .core import Finding

DEFAULT_PATHS = ["spicedb_kubeapi_proxy_tpu", "tests", "scripts",
                 "bench.py", "__graft_entry__.py"]
MAX_LINE = 100

# bounded-cardinality metric label names (M001).  Everything here has a
# value set bounded by configuration or schema — never by traffic.
ALLOWED_METRIC_LABELS = frozenset((
    "verb", "code", "phase", "backend", "resource", "reason", "stage",
    "decision", "generation", "kind", "le", "bucket", "slo", "window",
    "cause", "mode", "shard", "tier",
    # sweep telemetry: which fixpoint kernel produced the measurement
    # (ell | segment — bounded by the code, not by traffic)
    "kernel",
    # per-shard HBM accounting: owning device id of a sharded mesh
    # buffer (bounded by the local device count, not by traffic)
    "device",
    # Leopard fragment maintenance state (indexed | quarantined |
    # retired — bounded by the code, not by traffic)
    "state",
    # the jax platform and device kind a process serves from (one
    # series per process)
    "platform", "device_kind",
))
_METRIC_FACTORIES = ("counter", "gauge", "histogram")
_M001_PREFIX = "spicedb_kubeapi_proxy_tpu"

_HOTPATH_BEGIN = "hotpath: begin"
_HOTPATH_END = "hotpath: end"
_M003_NP = re.compile(
    r"(?<![A-Za-z_0-9])np\."
    r"(?!(ndarray|dtype|int32|int64|uint32|uint8|float32|bool_)\b)")
_M003_LOOP = re.compile(r"^\s*(async\s+)?(for|while)\b")

_METRICS_DOC = Path("docs/observability.md")
_DYNAMIC_METRIC_PREFIXES = ("authz_backend",)

# the analyzer's rule-fixture corpus is intentionally buggy
_SKIP_DIRS = frozenset(("__pycache__", "analysis_fixtures"))


def iter_py(paths):
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not _SKIP_DIRS.intersection(f.parts):
                    yield f
        elif p.suffix == ".py":
            yield p


class Visitor(ast.NodeVisitor):
    def __init__(self, findings, path, metric_families=None):
        self.findings = findings
        self.path = path
        self.imports: dict = {}
        self.used: set = set()
        self.metric_families = metric_families

    def _add(self, lineno, code, msg):
        self.findings.append(Finding(code, str(self.path), lineno, msg))

    def visit_Import(self, node):
        for a in node.names:
            name = (a.asname or a.name).split(".")[0]
            self.imports[name] = node.lineno
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if node.module == "__future__":
            return
        for a in node.names:
            if a.name == "*":
                continue
            self.imports[a.asname or a.name] = node.lineno
        self.generic_visit(node)

    def visit_Name(self, node):
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self._add(node.lineno, "E722", "bare `except:`")
        self.generic_visit(node)

    def _check_defaults(self, node):
        for d in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                self._add(d.lineno, "B006", "mutable default argument")

    def visit_FunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_Compare(self, node):
        for op, cmp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)):
                if isinstance(cmp, ast.Constant) and cmp.value is None:
                    self._add(node.lineno, "E711",
                              "comparison to None with ==/!= "
                              "(use is/is not)")
        self.generic_visit(node)

    def visit_Call(self, node):
        self._check_metric_labels(node)
        self.generic_visit(node)

    def _check_metric_labels(self, node):
        """M001: registry.counter/gauge/histogram(labels=(...)) label
        names must come from the bounded-cardinality allowlist."""
        if _M001_PREFIX not in Path(self.path).parts:
            return
        fn = node.func
        if not (isinstance(fn, ast.Attribute)
                and fn.attr in _METRIC_FACTORIES):
            return
        if (self.metric_families is not None and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("authz_")):
            self.metric_families[node.args[0].value] = (
                self.path, node.lineno)
        label_values = [kw.value for kw in node.keywords
                        if kw.arg == "labels"]
        if len(node.args) >= 3:
            label_values.append(node.args[2])
        for value in label_values:
            if not isinstance(value, (ast.Tuple, ast.List)):
                self._add(node.lineno, "M001",
                          "metric labels must be a literal tuple/list so "
                          "the cardinality gate can verify the names")
                continue
            for el in value.elts:
                if not (isinstance(el, ast.Constant)
                        and isinstance(el.value, str)):
                    self._add(el.lineno, "M001",
                              "metric label name must be a string literal")
                    continue
                if el.value not in ALLOWED_METRIC_LABELS:
                    self._add(el.lineno, "M001",
                              f"metric label {el.value!r} is not in the "
                              f"bounded-cardinality allowlist "
                              f"(identities belong in audit events, not "
                              f"metric labels)")


def lint_file(path, findings, metric_families=None):
    text = path.read_text()
    try:
        tree = ast.parse(text, filename=str(path))
    except SyntaxError as e:
        findings.append(Finding("E999", str(path), e.lineno or 0,
                                f"syntax error: {e}"))
        return
    v = Visitor(findings, path, metric_families=metric_families)
    v.visit(tree)

    src_names = v.used
    exempt = path.name == "__init__.py" or "__all__" in text
    if not exempt:
        for name, lineno in v.imports.items():
            if name not in src_names and f"{name}." not in text:
                findings.append(Finding("F401", str(path), lineno,
                                        f"unused import `{name}`"))

    seen: dict = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            if node.name in seen:
                findings.append(Finding(
                    "F811", str(path), node.lineno,
                    f"redefinition of `{node.name}` "
                    f"(first at line {seen[node.name]})"))
            seen[node.name] = node.lineno

    m003 = ("ops" in Path(path).parts
            and _M001_PREFIX in Path(path).parts)
    in_hotpath = False
    hotpath_open_line = 0
    for i, line in enumerate(text.splitlines(), 1):
        if line != line.rstrip():
            findings.append(Finding("W291", str(path), i,
                                    "trailing whitespace"))
        if len(line) > MAX_LINE:
            findings.append(Finding(
                "E501", str(path), i,
                f"line too long ({len(line)} > {MAX_LINE})"))
        stripped = line.lstrip(" ")
        if stripped.startswith("\t"):
            findings.append(Finding("TAB", str(path), i,
                                    "hard tab in indentation"))
        if not m003:
            continue
        if _HOTPATH_BEGIN in line:
            if in_hotpath:
                findings.append(Finding(
                    "M003", str(path), i,
                    f"nested hotpath fence (previous begin at line "
                    f"{hotpath_open_line} never ended)"))
            in_hotpath, hotpath_open_line = True, i
            continue
        if _HOTPATH_END in line:
            in_hotpath = False
            continue
        if not in_hotpath:
            continue
        code_part = line.split("#", 1)[0]
        if _M003_NP.search(code_part):
            findings.append(Finding(
                "M003", str(path), i,
                "host numpy (`np.`) inside a device hot-path fence — "
                "per-batch staging belongs on device (jnp) or outside "
                "the fence; this is the host-pack regression the "
                "device-resident pipeline removed"))
        if _M003_LOOP.match(code_part):
            findings.append(Finding(
                "M003", str(path), i,
                "per-item Python loop inside a device hot-path fence — "
                "batch it on device or move it outside the fence"))
    if m003 and in_hotpath:
        findings.append(Finding(
            "M003", str(path), hotpath_open_line,
            "hotpath fence never closed (`# hotpath: end` missing)"))


def _is_dynamic_family(name):
    return any(name == p or name.startswith(p + "_")
               for p in _DYNAMIC_METRIC_PREFIXES)


def check_metric_drift(metric_families, findings):
    """M002: the docs/observability.md metric catalog and the families
    package code actually registers must agree, both directions."""
    if not _METRICS_DOC.exists():
        findings.append(Finding("M002", str(_METRICS_DOC), 0,
                                "metrics doc missing "
                                "(docs/observability.md)"))
        return
    text = _METRICS_DOC.read_text()
    doc_names: dict = {}
    for i, line in enumerate(text.splitlines(), 1):
        for match in re.finditer(r"authz_[a-z0-9][a-z0-9_]*", line):
            doc_names.setdefault(match.group(0).rstrip("_"), i)
    for name, (path, lineno) in sorted(metric_families.items()):
        if _is_dynamic_family(name):
            continue
        if name not in doc_names:
            findings.append(Finding(
                "M002", str(path), lineno,
                f"metric family {name!r} is registered here but absent "
                f"from {_METRICS_DOC} — document it (operators cannot "
                f"use what the catalog does not name)"))
    code_names = set(metric_families)
    for name, lineno in sorted(doc_names.items()):
        if _is_dynamic_family(name):
            continue
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        if name not in code_names and base not in code_names:
            findings.append(Finding(
                "M002", str(_METRICS_DOC), lineno,
                f"doc names metric family {name!r} but no package code "
                f"registers it — a renamed or removed metric leaves "
                f"dashboards reading zeros"))


def run_legacy(paths=None) -> tuple:
    """-> (findings, n_files).  M002 (cross-file drift) runs only on a
    default-path (full-tree) invocation, same contract as before."""
    default_run = not paths
    paths = paths or DEFAULT_PATHS
    findings: list = []
    metric_families: dict = {}
    n = 0
    for f in iter_py(paths):
        n += 1
        lint_file(f, findings, metric_families=metric_families)
    if default_run:
        check_metric_drift(metric_families, findings)
    findings.sort(key=lambda f: (f.path, f.line))
    return findings, n
