"""Resolve a cell of BENCHMARK.json into its files, by name alone.

A cell `<config>.<mix>` names a configuration (its `file` in
BENCHMARK.json, under benchmark/configs/) and a traffic mix
(benchmark/traffic/<mix>.json).  A per-layer metric `<name>` is read by
benchmark/metrics/<name>.py, which defines `read(run) -> float | None`.
Adding a configuration, a mix or a metric is adding files and entries;
no code here changes.
"""

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file's contents
    mix: dict           # the traffic file's contents
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """An entry with `workloads` is reported where it lists; an end-to-end
    metric without one everywhere, a per-layer one wherever the metric it
    moves is."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(name: str, root: Path = ROOT,
            bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    with open(root / cfgs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "benchmark" / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer)


def reader(metric: str, root: Path = ROOT):
    """The `read` function of benchmark/metrics/<metric>.py."""
    path = root / "benchmark" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
