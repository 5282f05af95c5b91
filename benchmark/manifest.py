"""Finds everything a cell needs by the names in `BENCHMARK.json`.

For a cell (a `workloads` entry) under a benchmark directory B (the first
of `paths`):

- its configuration: the `file` of the `configs` entry it names; that JSON
  names its generator (`B/generators/<generator>.py`), schema and rules
  (beside the configuration file);
- its traffic: `B/traffic/<traffic>.json`;
- each metric it reports: `B/metrics/<name>.py`, a reader with `read(ctx)`;
  with `--trace 0` the `end_to_end` metrics, with `--trace 1` the
  `per_layer` ones, each where its `workloads` list names the cell (or
  has no such list);
- the device peaks: `B/peaks.json`, keyed by device kind.

Adding a configuration, traffic mix, cell or metric is adding files and
entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os


class Cell:
    def __init__(self, manifest_path: str, workload: str):
        self.root = os.path.dirname(os.path.abspath(manifest_path))
        with open(manifest_path) as f:
            self.manifest = json.load(f)
        self.bench_dir = os.path.join(self.root, self.manifest["paths"][0])
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {manifest_path}")
        self.workload = cells[workload]
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config_path = os.path.join(self.root, self.config_entry["file"])
        with open(self.config_path) as f:
            self.config = json.load(f)
        self.config_dir = os.path.dirname(self.config_path)
        with open(self.config_file("schema")) as f:
            self.schema_text = f.read()
        with open(os.path.join(self.bench_dir, "traffic",
                               f"{self.workload['traffic']}.json")) as f:
            self.traffic = json.load(f)

    def config_file(self, key: str) -> str:
        return os.path.join(self.config_dir, self.config[key])

    def generator(self):
        return load_module(os.path.join(
            self.bench_dir, "generators", f"{self.config['generator']}.py"))

    def metrics(self, traced: bool) -> list:
        """[(metric entry, reader module)] this cell reports."""
        out = []
        for m in self.manifest["per_layer" if traced else "end_to_end"]:
            if self.workload["name"] in m.get("workloads",
                                              [self.workload["name"]]):
                out.append((m, load_module(os.path.join(
                    self.bench_dir, "metrics", f"{m['name']}.py"))))
        return out

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.bench_dir, "peaks.json")) as f:
            table = json.load(f)
        if device_kind not in table:
            raise KeyError(f"no published peaks for device kind "
                           f"{device_kind!r} in peaks.json")
        return table[device_kind]


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_") \
        .replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
