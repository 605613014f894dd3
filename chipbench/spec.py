"""Resolve a benchmark cell by name into the files that define it.

``BENCHMARK.json`` at the root of the checkout names every cell
(``workloads``), its configuration and its traffic mix, and the
per-layer metrics. Each of those lives in a file of its own that is
found by name alone:

    chipbench/configs/<config>.json    sizes, the deployment, the limits
    chipbench/mixes/<traffic>.json     the solve's parameters
    chipbench/metrics/<metric>.py      a reader: ``read(ctx) -> float | None``

so a new cell, mix or metric is a new file plus a new entry in
``BENCHMARK.json``, and no file here is edited for it.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of the benchmark whose ``BENCHMARK.json`` is
    at ``root``, with its configuration and mix loaded."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "chipbench" / "mixes" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    # a metric without a workloads list goes with every cell that
    # reports the end-to-end metric it moves
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``chipbench/metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + "".join(c if c.isalnum() else "_"
                                      for c in metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
