"""Every cell resolves its files by name, and a new cell, mix or metric
needs only new files and a new entry in BENCHMARK.json."""
from __future__ import annotations

import json
import re

from conftest import ROOT, cpu_devices, tiny_config, write_tree

from chipbench import run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_workload_resolves_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.mix["name"] == w["traffic"]
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                       "time_to_eps_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))


def test_benchmark_names_and_keys():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= 1


def test_a_cell_of_new_files_only(tmp_path):
    """A configuration, a mix and a metric added as files, with entries
    in BENCHMARK.json, run without an edit to any file of the harness."""
    root = write_tree(
        tmp_path, {"new-k2": tiny_config("new-k2", 2, "run", rows=512,
                                         features=16)},
        [{"name": "new.h3", "config": "new-k2", "traffic": "h3-new",
          "chips": 1, "why": "test"}],
        per_layer=[{"name": "solves_traced", "unit": "solves",
                    "better": "higher", "source": "program_counter",
                    "layer": "test", "moves": "time_to_eps_s"}])
    (root / "chipbench" / "mixes" / "h3-new.json").write_text(json.dumps(
        {"name": "h3-new", "H": 3, "exchange": "persistent", "eps": 1e-3,
         "max_rounds": 5000}))
    (root / "chipbench" / "metrics" / "solves_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.solves)\n")
    cell = spec.resolve("new.h3", root)
    assert cell.mix["H"] == 3 and cell.config["trainer"]["K"] == 2
    result = run.run("new.h3", 7, 0.5, True, root=root,
                     devices_for=cpu_devices)
    assert result["correct"], result["checks"]
    assert result["metrics"]["solves_traced"]["value"] >= 1
    assert result["metrics"]["solves_traced"]["unit"] == "solves"
