"""Whole runs of the harness at a tiny size on the CPU, and the control.

A run without a TPU has to fail at the device check and print no result.
With the device check skipped, the rest of a run is driven end to end:
a sound run is `correct`, and each fault the cells can have, planted under
the timed path, makes `correct` come out false.
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
from manifest import Cell
from reference import Reference

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join(BENCH, "run.py")
CELLS = ("mt150k-mix", "rbac-list")


def harness(manifest: str, workload: str, seed: int, *extra) -> tuple:
    """-> (exit code, last stdout line parsed or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", manifest, "--workload", workload,
         "--seed", str(seed), "--seconds", "4", "--trace", "0", *extra],
        env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except ValueError:
            result = None
    return proc.returncode, result, proc.stderr


def test_without_a_tpu_the_run_fails_at_the_device_check(tiny_manifest):
    rc, result, err = harness(tiny_manifest, "mt150k-mix", 2**31 + 5)
    assert rc != 0
    assert result is None
    assert "device check" in err


def test_a_directory_without_the_program_fails(tmp_path):
    from conftest import make_tiny_root
    manifest = make_tiny_root(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(str(tmp_path), "benchmark", "run.py"),
         "--workload", "rbac-list", "--seed", "9", "--seconds", "2",
         "--trace", "0"], cwd=str(tmp_path), env=env,
        capture_output=True, text=True, timeout=300)
    assert os.path.exists(manifest)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.fixture(scope="module")
def fault_runs(tiny_manifest):
    """Every (cell, fault) run, started together."""
    jobs = {(cell, fault): (cell, 2**31 + 100 + i,
                            "--skip-device-check",
                            *(["--fault", fault] if fault else []))
            for i, (cell, fault) in enumerate(
                (c, f) for c in CELLS for f in ("", "answer", "half_batch"))}
    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as pool:
        futs = {k: pool.submit(harness, tiny_manifest, *v)
                for k, v in jobs.items()}
        return {k: f.result() for k, f in futs.items()}


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(fault_runs, cell):
    rc, result, err = fault_runs[(cell, "")]
    assert rc == 0, err[-3000:]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", ("answer", "half_batch"))
@pytest.mark.parametrize("cell", CELLS)
def test_a_fault_under_the_timed_path_is_not_correct(fault_runs, cell,
                                                     fault):
    rc, result, err = fault_runs[(cell, fault)]
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    wrong = sum(c["value"] for c in result["checks"].values())
    assert wrong > 0
    assert "check wrong_lists" in err


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tiny_manifest, cell):
    """The reference with the configuration's guarantee broken, in the
    program's place, over the requests a window sends: it has to fail the
    comparison on every seed."""
    c = Cell(tiny_manifest, cell)
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        data = run.build_data(c, seed)
        if c.traffic["loop"] == "open":
            sched = traffic.open_schedule(c.traffic, c.config["requests"],
                                          data, seed, 40.0)
            recs = [Record(k, u, ns, n, t) for t, k, u, ns, n in sched]
        else:
            streams = traffic.closed_streams(
                c.traffic, c.config["requests"], data, seed)
            recs = [Record(*next(s), intended=0.0) for s in streams
                    for _ in range(50)]
        ref = Reference(c.schema_text, data["relationships"])
        counts = run.compare(c, data, ref, recs, seed, control=True)
        wrong = sum(v for k, v in counts.items() if k.startswith("wrong_"))
        assert wrong > 0, (seed, counts)


@pytest.mark.parametrize("cell", CELLS)
def test_the_reference_agrees_with_the_programs_host_evaluator(
        tiny_manifest, cell):
    """A second witness: the program's own host oracle gives the plain
    reference's answers on the tiny graphs."""
    sys.path.insert(0, os.path.dirname(BENCH))
    from spicedb_kubeapi_proxy_tpu.spicedb import schema as sch
    from spicedb_kubeapi_proxy_tpu.spicedb.evaluator import Evaluator
    from spicedb_kubeapi_proxy_tpu.spicedb.store import TupleStore
    from spicedb_kubeapi_proxy_tpu.spicedb.types import SubjectRef

    c = Cell(tiny_manifest, cell)
    schema_text = c.schema_text
    data = run.build_data(c, 77)
    store = TupleStore()
    store.bulk_load_text("\n".join(data["relationships"]))
    oracle = Evaluator(sch.parse_schema(schema_text), store)
    ref = Reference(schema_text, data["relationships"])
    pods = [f"{ns}/{n}" for ns, names in data["pods_by_ns"].items()
            for n in names]
    for user in data["users"][:40]:
        want = set(oracle.lookup_resources("pod", "view",
                                           SubjectRef("user", user)))
        got = {p for p in pods if ref.check("pod", p, "view", user)}
        assert got == want, user
