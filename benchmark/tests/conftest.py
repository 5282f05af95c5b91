import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

# the cells' configurations at a size a test run holds; every other key,
# and the harness itself, as committed
TINY = {
    "multitenant-150k": dict(n_tenants=10, n_users=600, n_groups=60,
                             n_namespaces=40, n_pods=2000, n_tuples=12000),
    "rbac-deny-3k": dict(n_users=200, n_groups=10, n_pods=3000,
                         n_namespaces=5),
}


def make_tiny_root(dest: str) -> str:
    """A benchmark root (BENCHMARK.json + benchmark/) with tiny configs;
    -> the manifest path."""
    shutil.copytree(BENCH, os.path.join(dest, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    for name, params in TINY.items():
        path = os.path.join(dest, "benchmark", "configs", f"{name}.json")
        with open(path) as f:
            cfg = json.load(f)
        cfg["params"].update(params)
        with open(path, "w") as f:
            json.dump(cfg, f)
    for name in os.listdir(os.path.join(dest, "benchmark", "traffic")):
        path = os.path.join(dest, "benchmark", "traffic", name)
        with open(path) as f:
            spec = json.load(f)
        spec["warmup_s"] = 2
        if "clients" in spec:
            spec["clients"] = 4
            spec["ramp_s"] = 1
        with open(path, "w") as f:
            json.dump(spec, f)
    return os.path.join(dest, "BENCHMARK.json")


@pytest.fixture(scope="session")
def tiny_manifest(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))
