"""Finds everything that belongs to one cell by the names in
BENCHMARK.json: the configuration's file, the traffic mix's file
(traffic/<traffic>.json), the cell's limits (limits/<workload>.json), the
driver (drivers/<kind>.py) and the per-layer readers (metrics/<metric>.py).
A later PR adds a cell, a configuration or a metric by adding files and
entries; nothing here names one."""

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "pb_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_")
    name = name.rsplit(".py", 1)[0].replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with its files read."""

    def __init__(self, name, chips, cfg, traffic, limits, bench=None,
                 t_start=None, out_dir=None):
        import time

        self.name, self.chips = name, chips
        self.cfg, self.traffic, self.limits = cfg, traffic, limits
        self.bench = bench or {}
        self.t_start = time.time() if t_start is None else t_start
        self.out_dir = out_dir or os.path.join(ROOT, ".perfbench_out")

    @staticmethod
    def path(rel):
        return os.path.join(BENCH_DIR, rel)

    def out_path(self, name):
        os.makedirs(self.out_dir, exist_ok=True)
        return os.path.join(self.out_dir, name)

    def metrics(self, group):
        """The entries of `end_to_end` or `per_layer` that this cell
        reports: those without a `workloads` key, and those that list it."""
        return [m for m in self.bench.get(group, [])
                if self.name in m.get("workloads", [self.name])]


def load_cell(workload, t_start=None):
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    return Cell(
        workload, entry["chips"],
        cfg=load_json(os.path.join(ROOT, config["file"])),
        traffic=load_json(Cell.path(f"traffic/{entry['traffic']}.json")),
        limits=load_json(Cell.path(f"limits/{workload}.json"))["limits"],
        bench=bench, t_start=t_start)


def load_driver(kind):
    return load_module(Cell.path(f"drivers/{kind}.py"))


def load_reader(metric_name):
    return load_module(Cell.path(f"metrics/{metric_name}.py"))
