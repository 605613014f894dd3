"""Compile the main-path Pallas kernels for a described TPU v5e.

Each test compiles with ``interpret=False`` for a chip that is
described, not attached: the TPU compiler installed with jax refuses
here what the chip would refuse (a scalar stored to VMEM, a kernel
over its scoped VMEM), at no chip time. Nothing runs, so these say
nothing about results or speed. Sizes are the ones ``chip_smoke.py``
runs: m = 65,536, K = 8 workers, n_local = H = 1,024; the round guards
also compile the benchmark cells' rounds (m = 196,608; one chip with
K = 8, n_local = 250, and a 2x2 mesh with K = 4, n_local = 500), and
the SCD kernel is compiled at epsilon's full 400,000 rows.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, so describing it
while test files are collected would break the other test workers.
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tiling import column_rows

M, K, N_LOCAL, H = 65_536, 8, 1_024, 1_024
S = M // 128                 # rows of 128 lanes in a lane-tiled column
L = M                        # the exchanged update is the m-vector
TOPK_K = 656                 # topk(r=0.01) of L


@pytest.fixture(scope="module")
def v5e():
    """A described 2x2 v5e topology, with JAX's persistent compilation
    cache off: a compile for a described chip is written to the cache
    but cannot be read back without one."""
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    """One chip of the described topology."""
    return SingleDeviceSharding(v5e.devices[0])


def _compiled(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the program"
    return compiled


def _compile(fn, *shapes) -> str:
    return _compiled(fn, *shapes).as_text()


def _scd_program(text: str) -> str:
    """The Mosaic program of the first ``scd`` kernel in compiled HLO
    text, without source locations."""
    import base64

    from jax._src.lib.mlir import ir

    line = next(ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and re.search(r"%?scd[.\d]* =", ln))
    body = base64.b64decode(re.search(r'"body":"([^"]*)"', line).group(1))
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(body).operation.get_asm(
            enable_debug_info=False)


def _vmem(program: str) -> list[str]:
    """The shapes of the VMEM buffers a Mosaic program declares."""
    return re.findall(r"memref<([\dx]+)x\w+, #tpu.memory_space<vmem>>",
                      program)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_scd_kernel_compiles(one_chip):
    from repro.kernels.scd import scd_pallas

    s = functools.partial(_shape, one_chip)
    _compile(lambda A, q, i, a, w: scd_pallas(
        A, q, i, a, w, sigma=float(K), lam_eta=1.0, lam_l1=0.0,
        interpret=False),
        s((N_LOCAL, S, 128), jnp.float32), s((H,), jnp.float32),
        s((H,), jnp.int32), s((N_LOCAL,), jnp.float32),
        s((M,), jnp.float32))


def test_scd_kernel_compiles_at_full_epsilon_rows(one_chip):
    """epsilon's published 400,000 rows (S = 3,128 lane rows a column):
    the kernel's VMEM is its column ring plus w and rho whatever H is,
    so a worker of n_local = H = 250 fits the chip's scoped VMEM."""
    from repro.kernels.scd import scd_pallas

    m, n_local = 400_000, 250
    s = functools.partial(_shape, one_chip)
    _compile(lambda A, q, i, a, w: scd_pallas(
        A, q, i, a, w, sigma=float(K), lam_eta=1.0, lam_l1=0.0,
        interpret=False),
        s((n_local, column_rows(m), 128), jnp.float32),
        s((n_local,), jnp.float32), s((n_local,), jnp.int32),
        s((n_local,), jnp.float32), s((m,), jnp.float32))


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


def _virtual_round(sharding, exchange, m, n_local, H_, workers=K,
                   text=True):
    """Compiled HLO text (or, with ``text=False``, the compiled program)
    of a virtual-driver CoCoA round of ``workers`` workers on the
    lane-tiled (K, n_local, column_rows(m), 128) stack, with the kernel
    dispatch steered to the chip path."""
    from repro.core import distributed as dist
    from repro.core.cocoa import CoCoAConfig, _CoCoARound, _get_solver
    from repro.core.glm import GLMProblem

    Kw = workers
    cfg = CoCoAConfig(K=Kw, H=H_, solver="scd_kernel", exchange=exchange,
                      partitioner="block")
    algo = _CoCoARound(cfg, GLMProblem(), _get_solver(cfg.solver))
    s = functools.partial(_shape, sharding)
    data = (s((Kw, n_local, column_rows(m), 128), jnp.float32),
            s((Kw, n_local), jnp.float32), s((Kw, n_local), jnp.float32))
    rf = dist.build_virtual_round(algo, cfg.exchange, data, K=Kw,
                                  use_map=True)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=sharding)
    compiled = _compiled(rf.jitted, data, s((Kw, n_local), jnp.float32),
                         s((m,), jnp.float32), key, s((), jnp.int32))
    return compiled.as_text() if text else compiled


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
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    text = _virtual_round(one_chip, exchange, M, N_LOCAL, H)
    assert kernels <= pallas_kernels(text)


# ops that move no data of their own: control flow, tuples, views
_NO_DATA = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "conditional", "call"}


def _block_sized(text: str, elements: int) -> list[str]:
    """Instructions outside fusion bodies and outside the Pallas kernel
    whose result holds an f32 array of at least ``elements``."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    comp, out = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        inst = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(",
                        line)
        if (not inst or comp in fused or inst.group(3) in _NO_DATA
                or "tpu_custom_call" in line):
            continue
        dims = re.findall(r"f32\[([\d,]+)\]", inst.group(2))
        if any(math.prod(map(int, d.split(","))) >= elements for d in dims):
            out.append(inst.group(1))
    return out


@pytest.mark.parametrize("H_", [250, 16])
def test_cell_round_keeps_resident_scd_kernel(one_chip, monkeypatch, H_):
    """At the one-chip epsilon cells' shapes (m = 196,608, n_local = 250)
    the round's kernel is the resident form: a ring of NBUF whole
    (1,536, 128) columns and whole w and rho tiles in VMEM, no row
    tiles."""
    from repro.kernels.scd import NBUF
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    text = _virtual_round(one_chip, "persistent", 196_608, 250, H_)
    vmem = _vmem(_scd_program(text))
    assert f"{NBUF}x1536x128" in vmem, vmem
    assert all(v.endswith("1536x128") for v in vmem), vmem


@pytest.mark.parametrize("H_", [250, 16])
def test_cell_round_copies_no_block_around_scd(one_chip, monkeypatch, H_):
    """The one-chip benchmark cells' round (K = 8, m = 196,608,
    n_local = 250): ``lax.map`` hands each worker's kernel the whole
    stack and the worker's index, and the kernel fetches its visited
    columns from the stack at that row, so outside the kernel nothing of
    half a worker's block or more is written: no per-worker slice of
    the stack either. A round that slices each worker's block out of the
    stack writes one such copy; one that gathers the (H, m) columns with
    XLA relays the block out several times over."""
    from repro.analysis.graph import pallas_kernels
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    m, n_local = 196_608, 250
    text = _virtual_round(one_chip, "persistent", m, n_local, H_)
    assert "scd" in pallas_kernels(text)
    big = _block_sized(text, m * n_local // 2)
    assert len(big) == 0, big


def test_four_chip_cell_round_copies_no_block_around_scd(v5e, monkeypatch):
    """The four-chip benchmark cell's sharded round on a 2x2 mesh
    (K = 4, one worker a chip, m = 196,608, n_local = H = 500): each
    chip's kernel fetches its columns from its shard of the stack, so
    outside the kernel nothing of half a worker's block or more is
    written, and the update is summed by one all-reduce."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.analysis.graph import pallas_kernels
    from repro.core import distributed as dist
    from repro.core.cocoa import CoCoAConfig, _CoCoARound, _get_solver
    from repro.core.glm import GLMProblem
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    K4, m, n_local = 4, 196_608, 500
    mesh = Mesh(np.array(v5e.devices[:K4]), ("workers",))
    part, rep = NamedSharding(mesh, P("workers")), NamedSharding(mesh, P())
    cfg = CoCoAConfig(K=K4, H=n_local, solver="scd_kernel",
                      exchange="persistent", partitioner="block")
    algo = _CoCoARound(cfg, GLMProblem(), _get_solver(cfg.solver))
    data = (_shape(part, (K4, n_local, m // 128, 128), jnp.float32),
            _shape(part, (K4, n_local), jnp.float32),
            _shape(part, (K4, n_local), jnp.float32))
    rf = dist.build_sharded_round(algo, cfg.exchange, data, mesh)
    text = _compile(rf.jitted, data, _shape(part, (K4, 2), jnp.uint32),
                    _shape(part, (K4, n_local), jnp.float32),
                    _shape(rep, (m,), jnp.float32),
                    _shape(rep, (), jnp.int32))
    assert "scd" in pallas_kernels(text)
    assert "all-reduce" in text
    big = _block_sized(text, m * n_local // 2)
    assert len(big) <= 1, big


# HIGGS at its published 11,000,000 x 28, K = 4 workers on one chip
HIGGS_M, HIGGS_K, HIGGS_N_LOCAL = 11_000_000, 4, 7
HIGGS_S = column_rows(HIGGS_M)      # 85,944 rows of 128 lanes


def test_scd_kernel_compiles_row_tiled_at_higgs_rows(one_chip):
    """A 44 MB column (S = 85,944 lane rows) is far over the scoped
    VMEM, so the kernel compiles its row-tiled form: only tiles of rows
    in VMEM (none a whole column), w and rho left in HBM."""
    from repro.kernels.scd import _tile_rows, scd_pallas

    s = functools.partial(_shape, one_chip)
    n, H_ = HIGGS_N_LOCAL, HIGGS_N_LOCAL
    text = _compile(lambda A, q, i, a, w: scd_pallas(
        A, q, i, a, w, sigma=float(HIGGS_K), lam_eta=1.0, lam_l1=0.0,
        interpret=False),
        s((n, HIGGS_S, 128), jnp.float32), s((H_,), jnp.float32),
        s((H_,), jnp.int32), s((n,), jnp.float32),
        s((HIGGS_M,), jnp.float32))
    T = _tile_rows(HIGGS_S)
    vmem = _vmem(_scd_program(text))
    assert f"2x2x{T}x128" in vmem, vmem
    assert not any(str(HIGGS_S) in v for v in vmem), vmem


def test_higgs_cell_round_compiles(one_chip, monkeypatch):
    """The whole virtual-driver round of the HIGGS cell (K = 4,
    n_local = H = 7, m = 11,000,000) compiles for one chip, with the
    row-tiled kernel in it, and fits the chip: the stack (1.23 GB), the
    (K, m) updates and the m-vectors. No instruction outside the kernel
    has a worker block's shape: the kernel reads its block from the
    stack in place. The (K, m) stack that ``lax.map`` writes the
    workers' updates into, as large as a block here (7 columns), is
    allowed: it is the map's output, not a copy of the data."""
    from repro.analysis.graph import pallas_kernels
    from repro.utils import compat

    monkeypatch.setattr(compat, "on_tpu", lambda: True)
    compiled = _virtual_round(one_chip, "persistent", HIGGS_M,
                              HIGGS_N_LOCAL, HIGGS_N_LOCAL,
                              workers=HIGGS_K, text=False)
    text = compiled.as_text()
    assert "scd" in pallas_kernels(text)
    assert not any(str(HIGGS_S) in v for v in _vmem(_scd_program(text)))
    block = re.compile(rf"f32\[(1,)?{HIGGS_N_LOCAL},{HIGGS_S},128\]")
    assert not [ln for ln in text.splitlines()
                if block.search(ln) and "tpu_custom_call" not in ln]
    mem = compiled.memory_analysis()
    stack, vector = HIGGS_K * HIGGS_N_LOCAL * HIGGS_S * 512, HIGGS_S * 512
    assert mem.argument_size_in_bytes < stack + 2 * vector   # and w
    assert mem.temp_size_in_bytes < stack


def test_higgs_stack_uploads_without_lane_padding(one_chip):
    """The packed (K, n_pad, S, 128) stack of the HIGGS cell takes its
    own size on the chip (its minor dimensions are a column's rows of
    lanes, padded to 8 rows at most), and the device program that
    follows the upload, the columns' norms, holds less than the stack
    and one (K, m) stack of m-vectors besides."""
    from repro.core.partition import column_norms

    s = functools.partial(_shape, one_chip)
    shape = (HIGGS_K, HIGGS_N_LOCAL, HIGGS_S, 128)
    stack = math.prod(shape) * 4
    mem = column_norms.lower(s(shape, jnp.float32)).compile() \
        .memory_analysis()
    assert mem.argument_size_in_bytes <= stack * (HIGGS_S + 7) // HIGGS_S
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < stack + HIGGS_K * HIGGS_S * 512
