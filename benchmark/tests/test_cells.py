"""A cell resolves from files alone: a new configuration, traffic mix and
per-layer metric placed as files, with entries in BENCHMARK.json, make a
cell with no edit to any file the benchmark already has."""

import json
import shutil

from benchmark import cells
from benchmark.cells import ROOT


def test_new_files_make_a_cell(tmp_path):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["topology"]["tiles"]["verify"]["batch"] = 1024
    (tmp_path / "benchmark/configs/newcfg.json").write_text(json.dumps(cfg))
    mix = json.loads(
        (ROOT / "benchmark/traffic/transfer_firehose.json").read_text())
    mix["outstanding"] = 1234
    (tmp_path / "benchmark/traffic/newmix.json").write_text(json.dumps(mix))
    (tmp_path / "benchmark/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    bench["configs"].append({"name": "newcfg", "source": "x",
                             "file": "benchmark/configs/newcfg.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "newcfg.newmix", "config": "newcfg",
                               "traffic": "newmix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "sigs_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.resolve("newcfg.newmix", root=tmp_path)
    assert cell.config["topology"]["tiles"]["verify"]["batch"] == 1024
    assert cell.mix["outstanding"] == 1234
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "sigs_per_s"}
    assert cells.reader("new_metric", root=tmp_path)(True) == 42.0
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "benchmark").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[k] == v for k, v in before.items()
               if "__pycache__" not in k.parts)


def test_every_cell_resolves_with_its_metrics():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"], bench=bench)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(cells.reader(m["name"]))
