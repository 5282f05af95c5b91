"""A004 — feature-gate hygiene for killswitch-gated subsystems.

Every gated subsystem in this repo ships with the same hand-tested
invariant: "gate off must mean inert" (tripwire tests monkeypatch the
gated entry points to raise).  The mechanical version: inside a gated
module, a function that MUTATES subsystem state — bumps a metric
(`.inc()`/`.observe()`/`.dec()`), rebinds a module global (`global x`
then `x = ...`), or appends/records into a module-level registry — must
be dominated by a gate check: a call or flag read whose name says
"enabled" appearing before the mutation in the same function, or (for
private helpers) in every same-module caller.  A public mutator with no
dominating check is exactly how an "inert" killswitch quietly keeps
counting, queueing, or journaling.

The module -> gate map below is the subsystem registry; extend it when
a new gated subsystem lands (the gate name is printed in the finding so
the fix is obvious either way).
"""

from __future__ import annotations

import ast

from .core import attr_chain

# package-relative path fragment -> gate name (utils/features.py)
GATED_MODULES = (
    # the directory fragment covers the whole replication subsystem:
    # leader.py, follower.py, AND the failover layer (failover.py —
    # promotion/fencing/fan-out) all ride the `Replication` gate
    ("spicedb/replication/", "Replication"),
    ("utils/admission.py", "AdmissionControl"),
    ("utils/timeline.py", "Timeline"),
    ("utils/devtel.py", "DeviceTelemetry"),
    ("spicedb/decision_cache.py", "DecisionCache"),
    ("spicedb/persist/", "DurableStore"),
    # the differential fuzz harness's authz_fuzz_* recording helpers
    # (the generators/driver/shrinker themselves are offline tooling
    # with no subsystem state to gate)
    ("fuzz/metrics.py", "FuzzTelemetry"),
    # partitioned write scale-out: the directory fragment covers the
    # whole sharding subsystem (partition map, revision vectors, the
    # router/endpoint compositions, and the authz_shard_* recording
    # helpers) under the `Sharding` killswitch
    ("spicedb/sharding/", "Sharding"),
    # kernel introspection & cost attribution: the sweep-telemetry
    # accounting plane (authz_sweep_* metrics + /debug/workload) rides
    # the KernelIntrospect gate; the sampling profiler has its own
    # killswitch because a blocking capture is a heavier hammer
    ("utils/workload.py", "KernelIntrospect"),
    ("utils/profiler.py", "Profiler"),
    # multi-chip mesh execution: the sharded kernel module rides the
    # MeshExecution killswitch (distributed.py is pure
    # runtime glue with no subsystem state to gate; the
    # endpoint checks the gate at mesh construction)
    ("parallel/sharding.py", "MeshExecution"),
    # Leopard materialized group index: the closure planner/builder and
    # its authz_leopard_* recording helpers ride the LeopardIndex
    # killswitch (the endpoint only constructs the index when the gate
    # was on at build time)
    ("ops/leopard.py", "LeopardIndex"),
    # tail explainer: pure report computation over the merged fleet
    # view; the explain() entry point checks the gate itself, and the
    # module keeps no state and ticks no metrics
    ("utils/tailexplain.py", "TailExplain"),
)

_MUTATOR_METHODS = ("inc", "observe", "dec")


def _gate_for(rel: str):
    if "spicedb_kubeapi_proxy_tpu" not in rel:
        return None
    for frag, gate in GATED_MODULES:
        if frag in rel:
            return gate
    return None


def _is_gate_check(node) -> bool:
    """A call or flag read whose terminal name says 'enabled'."""
    if isinstance(node, ast.Call):
        chain = attr_chain(node.func)
        return bool(chain) and "enabled" in chain[-1].lower()
    chain = attr_chain(node)
    return bool(chain) and "enabled" in chain[-1].lower()


def _has_gate_check(func: ast.AST, before_line=None) -> bool:
    for node in ast.walk(func):
        if _is_gate_check(node):
            if before_line is None or node.lineno <= before_line:
                return True
    return False


def _mutations(func, module_globals) -> list:
    """(line, description) mutation sites in one function body."""
    out = []
    declared_global: set = set()
    for node in ast.walk(func):
        if isinstance(node, ast.Global):
            declared_global.update(node.names)
    for node in ast.walk(func):
        if isinstance(node, ast.Call):
            chain = attr_chain(node.func)
            if chain and chain[-1] in _MUTATOR_METHODS:
                out.append((node.lineno,
                            f"metric mutation `{'.'.join(chain)}(...)`"))
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for tgt in targets:
                if (isinstance(tgt, ast.Name)
                        and tgt.id in declared_global):
                    out.append((node.lineno,
                                f"module global `{tgt.id}` rebound"))
    for node in ast.walk(func):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("append", "add", "appendleft")
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in module_globals):
            out.append((node.lineno,
                        f"module registry `{node.func.value.id}."
                        f"{node.func.attr}(...)`"))
    return out


def _class_exempt(src, cls) -> bool:
    """True when the `class Foo:` line carries `# noqa: A004(reason)` —
    the class-level declaration that its instances only exist when the
    gate is on (reason required, same contract as line suppressions)."""
    for code, reason in src.noqa.get(cls.lineno, ()):
        if code == "A004" and (reason or "").strip():
            return True
    return False


def rule_a004(sources) -> list:
    findings: list = []
    for src in sources:
        gate = _gate_for(src.rel)
        if gate is None:
            continue
        module_globals = {
            t.id for n in src.tree.body if isinstance(n, ast.Assign)
            for t in n.targets if isinstance(t, ast.Name)}
        funcs = {src.qualnames[id(n)]: n for n in ast.walk(src.tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        # same-module caller map (by bare name and self-method name)
        callers: dict = {}
        for qual, fn in funcs.items():
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    chain = attr_chain(node.func)
                    if not chain:
                        continue
                    name = chain[-1]
                    callers.setdefault(name, []).append(
                        (qual, node.lineno))
        for qual, fn in funcs.items():
            muts = _mutations(fn, module_globals)
            if not muts:
                continue
            name = qual.rsplit(".", 1)[-1]
            if name in ("__init__", "__post_init__"):
                continue  # construction wires state; gates act at use
            cls = src.enclosing_class(fn)
            if cls is not None and _class_exempt(src, cls):
                # constructed-behind-gate wrapper: the gate decides
                # whether the object EXISTS (create_endpoint / server
                # startup checks it), so call sites need no re-check —
                # declared by `# noqa: A004(reason)` on the class line
                continue
            for line, what in muts:
                if _has_gate_check(fn, before_line=line):
                    continue
                if name.startswith("_"):
                    # private helper: pass when every same-module caller
                    # is gate-checked before the call site
                    calls = callers.get(name, [])
                    if calls and all(
                            _has_gate_check(funcs[cq], before_line=cl)
                            for cq, cl in calls if cq in funcs):
                        continue
                findings.append(src.finding(
                    "A004", line,
                    f"{what} in `{qual}` ({gate}-gated module) has no "
                    f"dominating gate check — with the {gate} "
                    f"killswitch off this path must be inert"))
    return findings
