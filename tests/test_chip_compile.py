"""Compile the main-path Pallas kernels for a described TPU v5e.

Each test compiles with ``interpret=False`` for a chip that is
described, not attached: the TPU compiler installed with jax refuses
here what the chip would refuse (a scalar stored to VMEM, a kernel
over its scoped VMEM), at no chip time. Nothing runs, so these say
nothing about results or speed. Sizes are the ones ``chip_smoke.py``
runs: m = 65,536, K = 8 workers, n_local = H = 1,024.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, so describing it
while test files are collected would break the other test workers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

M, K, N_LOCAL, H = 65_536, 8, 1_024, 1_024
L = M                        # the exchanged update is the m-vector
TOPK_K = 656                 # topk(r=0.01) of L


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with JAX's persistent compilation cache
    off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_scd_kernel_compiles(one_chip):
    from repro.kernels.scd import scd_pallas

    s = functools.partial(_shape, one_chip)
    _compile(lambda c, q, i, a, w: scd_pallas(
        c, q, i, a, w, sigma=float(K), lam_eta=1.0, lam_l1=0.0,
        interpret=False),
        s((H, M), jnp.float32), s((H,), jnp.float32), s((H,), jnp.int32),
        s((N_LOCAL,), jnp.float32), s((M,), jnp.float32))


@pytest.mark.parametrize("codec", ["int8", "int4", "int2"])
def test_quantize_pack_compiles(one_chip, codec):
    from repro.kernels import quant

    fn = getattr(quant, f"quantize_pack_{codec}")
    _compile(lambda x: fn(x, interpret=False),
             _shape(one_chip, (L,), jnp.float32))


def test_batched_quantize_compiles(one_chip):
    """The virtual driver encodes all K workers' updates under vmap."""
    from repro.kernels.quant import quantize_pack_int8

    _compile(jax.vmap(lambda x: quantize_pack_int8(x, interpret=False)),
             _shape(one_chip, (K, L), jnp.float32))


@pytest.mark.parametrize("codec,width,dtype", [
    ("int8", L, jnp.int8), ("int4", L // 2, jnp.uint8),
    ("int2", L // 4, jnp.uint8)])
def test_decode_reduce_compiles(one_chip, codec, width, dtype):
    from repro.kernels import dequant

    fn = getattr(dequant, f"decode_reduce_{codec}")
    _compile(lambda p, s: fn(p, s, L, interpret=False),
             _shape(one_chip, (K, width), dtype),
             _shape(one_chip, (K,), jnp.float32))


def test_topk_select_compiles(one_chip):
    from repro.kernels.topk import topk_select

    _compile(lambda x: topk_select(x, TOPK_K, interpret=False),
             _shape(one_chip, (L,), jnp.float32))


@pytest.mark.parametrize("exchange,kernels", [
    ("persistent", {"scd"}),
    ("compressed:int8", {"scd", "quantize_pack_int8",
                         "decode_reduce_int8"})])
def test_cocoa_round_compiles_with_kernels(one_chip, monkeypatch,
                                           exchange, kernels):
    """A whole virtual-driver CoCoA round at the smoke size, with the
    kernel dispatch steered to the chip path: every main-path kernel is
    compiled into the round, none is left to the jnp oracle."""
    from repro.analysis.graph import pallas_kernels
    from repro.core import distributed as dist
    from repro.core.cocoa import CoCoAConfig, _CoCoARound, _get_solver
    from repro.core.glm import GLMProblem
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    cfg = CoCoAConfig(K=K, H=H, solver="scd_kernel", exchange=exchange)
    algo = _CoCoARound(cfg, GLMProblem(), _get_solver(cfg.solver))
    s = functools.partial(_shape, one_chip)
    data = (s((K, M, N_LOCAL), jnp.float32), s((K, N_LOCAL), jnp.float32),
            s((K, N_LOCAL), jnp.float32))
    rf = dist.build_virtual_round(algo, cfg.exchange, data, K=K,
                                  use_map=True)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    text = _compile(rf.jitted, data, s((K, N_LOCAL), jnp.float32),
                    s((M,), jnp.float32), key, s((), jnp.int32))
    assert kernels <= pallas_kernels(text)
