"""Compile each cell's round for a described TPU v5e, at the cell's real
shapes, without a chip.

The one-chip cells' virtual-driver round (K = 8, m = 196,608, n_local =
250, H = 250 and H = 16) is compiled for one described chip, and the
four-chip cell's sharded round (K = 4, n_local = H = 500) for a 2x2
mesh. Each must hold the Pallas SCD kernel as a ``tpu_custom_call`` and
fit the chip (the compiler refuses a kernel over its scoped VMEM or a
program over the device's memory); the sharded round must carry an
all-reduce. ``memory_analysis()`` and the kernels are printed (``-s``).
Nothing runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from conftest import ROOT


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _algo(K, H):
    from repro.core.cocoa import CoCoAConfig, _CoCoARound, _get_solver
    from repro.core.glm import GLMProblem

    cfg = CoCoAConfig(K=K, H=H, solver="scd_kernel", exchange="persistent",
                      partitioner="block")
    return cfg, _CoCoARound(cfg, GLMProblem(lam=cfg.lam),
                            _get_solver(cfg.solver))


def _report(name, compiled):
    from repro.analysis.graph import pallas_kernels

    text = compiled.as_text()
    kernels = pallas_kernels(text)
    print(f"{name}: kernels {sorted(kernels)}; {compiled.memory_analysis()}")
    return text, kernels


@pytest.mark.parametrize("H", [250, 16])
def test_one_chip_round_compiles(topo, monkeypatch, H):
    from repro.core import distributed as dist
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    cfg = _config("epsilon-cocoa-k8-1chip")
    K, m = cfg["trainer"]["K"], cfg["rows"]
    n_local = cfg["features"] // K
    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ccfg, algo = _algo(K, H)
    data = (s((K, m, n_local)), s((K, n_local)), s((K, n_local)))
    rf = dist.build_virtual_round(algo, ccfg.exchange, data, K=K,
                                  use_map=True)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one)
    compiled = rf.jitted.lower(data, s((K, n_local)), s((m,)), key,
                               s((), jnp.int32)).compile()
    _, kernels = _report(f"K={K} H={H} one chip", compiled)
    assert "scd" in kernels


def test_four_chip_sharded_round_compiles(topo, monkeypatch):
    from jax.sharding import Mesh

    from repro.core import distributed as dist
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    cfg = _config("epsilon-cocoa-k4-4chip")
    K, m = cfg["trainer"]["K"], cfg["rows"]
    n_local = cfg["features"] // K
    mesh = Mesh(np.array(topo.devices[:K]), ("workers",))
    part, rep = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())

    def s(shape, sharding, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ccfg, algo = _algo(K, n_local)
    data = (s((K, m, n_local), part), s((K, n_local), part),
            s((K, n_local), part))
    rf = dist.build_sharded_round(algo, ccfg.exchange, data, mesh)
    compiled = rf.jitted.lower(
        data, s((K, 2), part, jnp.uint32), s((K, n_local), part),
        s((m,), rep), s((), rep, jnp.int32)).compile()
    text, kernels = _report(f"K={K} H={n_local} 2x2 mesh", compiled)
    assert "scd" in kernels
    assert "all-reduce" in text
