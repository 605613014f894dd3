"""CPU tests of the chip benchmark: ``python -m pytest chipbench/tests``.

They run on the CPU with four host devices (the four-chip cell's
sharded driver needs them), at sizes a test run holds. The Pallas
kernels run in interpret mode here.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

TINY_DATA = {"factors": 4, "factor_share": 0.2, "label_noise": 0.33,
             "block_rows": 256}


def tiny_config(name, K, driver, rows=1024, features=64):
    return {"name": name, "rows": rows, "features": features,
            "data": TINY_DATA, "precision": "float32",
            "trainer": {"K": K, "lam": 1.0, "solver": "scd_kernel",
                        "partitioner": "block", "driver": driver},
            "limits": {"primal_gap": 1e-4}}


def write_tree(root: Path, configs: dict, workloads: list,
               per_layer=None) -> Path:
    """A benchmark tree at ``root``: BENCHMARK.json, the given
    configurations, and a copy of the repository's mixes and metric
    readers."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
    for sub in ("mixes", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, root / "chipbench" / sub,
                        dirs_exist_ok=True)
    spec["configs"] = []
    for name, cfg in configs.items():
        f = f"chipbench/configs/{name}.json"
        (root / f).write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test", "file": f,
                                "reduced": [], "why": "test"})
    spec["workloads"] = workloads
    if per_layer is not None:
        spec["per_layer"] = per_layer
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    """The repository's three cells at 1,024 x 64."""
    return write_tree(
        tmp_path,
        {"tiny-k8": tiny_config("tiny-k8", 8, "run"),
         "tiny-k4x4": tiny_config("tiny-k4x4", 4, "run_sharded")},
        [{"name": "tiny.h-local", "config": "tiny-k8",
          "traffic": "h-local-persistent", "chips": 1, "why": "test"},
         {"name": "tinyx4.h-local", "config": "tiny-k4x4",
          "traffic": "h-local-persistent", "chips": 4, "why": "test"},
         {"name": "tiny.h16", "config": "tiny-k8",
          "traffic": "h16-persistent", "chips": 1, "why": "test"}])


def cpu_devices(chips):
    """Stands in for the harness's look for a chip."""
    import jax

    return jax.devices()
