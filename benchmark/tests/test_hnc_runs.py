"""The hierarchical-namespace cell at a tiny size on the CPU: a sound run
is `correct`, a program that cuts the `parent->view` chain short is not,
and the window's answers need deep chains.
"""

import concurrent.futures
import json
import os
import subprocess
import sys

import pytest

import run
import traffic
from client import Record
from conftest import make_tiny_root
from manifest import Cell
from reference import Reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join(BENCH, "run.py")
FAULT = os.path.join(BENCH, "tests", "hnc_fault")
CELL = "hnc150k-mix"
# ten namespaces per tenant: a root and levels 1-8
TINY_HNC = dict(n_tenants=4, n_users=600, n_groups=60, n_namespaces=40,
                n_pods=2000, n_tuples=12000)


@pytest.fixture(scope="module")
def hnc_manifest(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("tiny-hnc"))
    manifest = make_tiny_root(dest)
    path = os.path.join(dest, "benchmark", "configs", "hnc-150k.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["params"].update(TINY_HNC)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return manifest


def _cut_schema(schema: str, hops: int) -> str:
    """The schema with `parent->view` followed at most `hops` levels: the
    namespace's view unrolled into view0..view{hops}, and pods reading
    the last."""
    local = "viewer + tenant->access"
    perms = [f"  permission view0 = {local}"]
    perms += [f"  permission view{k} = {local} + parent->view{k - 1}"
              for k in range(1, hops + 1)]
    out = schema.replace(f"  permission view = {local} + parent->view",
                         "\n".join(perms))
    out = out.replace("namespace->view -", f"namespace->view{hops} -")
    assert out.count(f"view{hops}") == 2, "the schema changed"
    return out


def _window(cell: Cell, seed: int, seconds: float) -> tuple:
    data = run.build_data(cell, seed)
    sched = traffic.open_schedule(cell.traffic, cell.config["requests"],
                                  data, seed, seconds)
    return data, [Record(k, u, ns, n, t) for t, k, u, ns, n in sched]


def deep_answers(cell: Cell, seed: int, seconds: float, hops: int) -> dict:
    """{request kind: answers of a window of `seconds` that differ when
    `parent->view` is cut at `hops` levels}, and the number compared."""
    data, recs = _window(cell, seed, seconds)
    full = Reference(cell.schema_text, data["relationships"])
    cut = Reference(_cut_schema(cell.schema_text, hops),
                    data["relationships"])
    reqs = cell.config["requests"]
    out = {k: 0 for k in reqs}
    for rec in sorted(recs, key=lambda r: r.user):
        spec = reqs[rec.kind]
        pods = data["pods_by_ns"]
        if run.expected(full, spec, rec, pods) != \
                run.expected(cut, spec, rec, pods):
            out[rec.kind] += 1
        full.forget(rec.user)
        cut.forget(rec.user)
    out["compared"] = len(recs)
    return out


def test_the_cut_schema_cuts_only_the_chain(hnc_manifest):
    cell = Cell(hnc_manifest, CELL)
    deep = deep_answers(cell, 2**31 + 41, 40.0, 8)
    assert deep["list"] == deep["get"] == 0


def test_the_window_needs_three_or_more_parent_hops(hnc_manifest):
    cell = Cell(hnc_manifest, CELL)
    deep = deep_answers(cell, 2**31 + 42, 40.0, 2)
    assert deep["list"] + deep["get"] > 0, deep


def harness(manifest: str, seed: int, fault: bool) -> tuple:
    """-> (exit code, last stdout line parsed or None, stderr) of a run
    without the device check, with the fault planted if `fault`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if fault:
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (FAULT, env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", CELL,
         "--seed", str(seed), "--seconds", "4", "--trace", "0",
         "--skip-device-check"],
        env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except ValueError:
        result = None
    return proc.returncode, result, proc.stderr


@pytest.fixture(scope="module")
def hnc_runs(hnc_manifest):
    """A sound run and one with the chain cut short, started together."""
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        futs = {f: pool.submit(harness, hnc_manifest, 2**31 + 50 + f, f)
                for f in (False, True)}
        return {f: fut.result() for f, fut in futs.items()}


def test_a_sound_hnc_run_is_correct(hnc_runs):
    rc, result, err = hnc_runs[False]
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_parent_chain_cut_short_is_not_correct(hnc_runs):
    rc, result, err = hnc_runs[True]
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    wrong = sum(c["value"] for c in result["checks"].values())
    assert wrong > 0
