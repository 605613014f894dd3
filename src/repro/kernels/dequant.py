"""Pallas TPU kernels: fused decode + reduce for the compressed
exchange's gather side.

The receive side of every quantizing exchange used to dequantize the
all-gathered ``(K, wire)`` payload into a ``(K, L)`` f32 stack in HBM
and then sum it — the ``f32-intermediate`` inefficiency the
``python -m repro.analysis`` linter flags cell by cell. These kernels
fuse the whole gather side into one VMEM pass: unpack, bias-shift,
scale by the per-worker f32 scale and accumulate the f32 sum (or mean)
worker by worker, so the only f32 tensor that ever exists is the
``(L,)``-sized accumulator — no K x L f32 HBM round-trip.

Layouts mirror the encode kernels in ``repro.kernels.quant``: the
``(K, wire)`` payload is laid out lane-dense as ``(K, R, 128)``
(``repro.kernels.tiling``) and the grid streams blocks of rows, each
independent of the others, with the K per-worker scales in SMEM:

  * int8: (K, R, 128) int8 payload -> (1, R, 128) f32.
  * int4: (K, R, 128) packed bytes -> (2, R, 128) f32 split-half
    planes (element ``i`` pairs with ``i + ceil(L/2)``), reshaped and
    sliced back to (L,) by the wrapper.
  * int2: (K, R, 128) packed bytes -> (4, R, 128) f32 split-quarter
    planes.

Reduction-order contract: the per-worker rows are accumulated
SEQUENTIALLY in canonical worker order (k = 0..K-1) and the mean is the
sum times the f32-rounded ``1/K`` — exactly the op sequence of the
``decode_stacked_ref`` oracle in ``repro.kernels.ref`` (which is also
the off-TPU path in ``repro.comm.codec``), so kernel and oracle are
bit-identical, pinned by tests and the ``kernels`` benchmark. Each
decoded row is walled off from the accumulate add by a
``where(isfinite(row), row, 0)`` select (``_no_fma``) so the compiler
cannot contract ``acc + q*scale`` into an FMA on one path but not the
other — observed on CPU, where the contracted chain is 1 ulp off the
strict one and ``lax.optimization_barrier`` does NOT stop it (the
contraction happens inside one fused loop at codegen, below HLO). The
select is semantically free: quantized products are finite by
construction. The wrappers pad the wire dimension to whole
row blocks with zero bytes (padded codes decode to exact zeros under every
codec's biased grid... int8's zero byte IS code 0; int4/int2 padded
bytes decode to the biased code -8/-2 times the scale but are sliced
off before they can be observed), run compiled on TPU and in interpret
mode everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, row_tiles, to_rows
from repro.utils import compat


def _no_fma(row: jax.Array) -> jax.Array:
    """Force the ``q*scale`` product to round to f32 before it reaches
    the accumulate add: routing it through a data-dependent select
    breaks the ``fadd(fmul, ..)`` pattern the backend would otherwise
    contract to an FMA (1 ulp off the strict chain, and immune to
    ``lax.optimization_barrier``, which sits above the fused-loop
    codegen where the contraction happens). ``isfinite`` is always true
    for quantized products, so the select never changes a value."""
    return jnp.where(jnp.isfinite(row), row, jnp.float32(0.0))


def _unpack_int8(q):
    return (q,)


def _unpack_int4(p):
    p = p.astype(jnp.int32)
    return (p & 0xF) - 8, (p >> 4) - 8


def _unpack_int2(p):
    p = p.astype(jnp.int32)
    return tuple(((p >> (2 * f)) & 0x3) - 2 for f in range(4))


def _decode_kernel(K: int, mult: float | None, unpack, s_ref, p_ref,
                   out_ref):
    """Sequential f32 accumulation over the K decoded worker rows, one
    accumulator per packed plane — the ONE reduction-order contract
    shared with the jnp oracle."""
    acc = None
    for k in range(K):
        rows = [_no_fma(q.astype(jnp.float32) * s_ref[k])
                for q in unpack(p_ref[k])]
        acc = rows if acc is None else [a + r for a, r in zip(acc, rows)]
    for f, a in enumerate(acc):
        out_ref[f] = a if mult is None else a * mult


_UNPACK = {"int8": (_unpack_int8, 1), "int4": (_unpack_int4, 2),
           "int2": (_unpack_int2, 4)}


@functools.partial(jax.jit,
                   static_argnames=("codec", "length", "mean", "interpret"))
def _decode_reduce(codec: str, payload: jax.Array, scales: jax.Array,
                   length: int, mean: bool, interpret: bool | None
                   ) -> jax.Array:
    unpack, planes = _UNPACK[codec]
    K, W = payload.shape
    block, rows = row_tiles(W)
    # None = plain sum (no trailing multiply); the mean is the sum
    # times the f32-rounded 1/K, same constant the oracle uses
    mult = (1.0 / K) if mean else None
    out = pl.pallas_call(
        functools.partial(_decode_kernel, K, mult, unpack),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),        # scales
                  pl.BlockSpec((K, block, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((planes, block, LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((planes, rows, LANE), jnp.float32),
        interpret=compat.default_interpret(interpret),
        name=f"decode_reduce_{codec}",
    )(scales.reshape(K).astype(jnp.float32), to_rows(payload, rows))
    return out.reshape(planes, rows * LANE)[:, :W].reshape(-1)[:length]


def decode_reduce_int8(q: jax.Array, scales: jax.Array, length: int, *,
                       mean: bool = True, interpret: bool | None = None
                       ) -> jax.Array:
    """Fused decode+reduce of an all-gathered int8 payload: ``(K, L)``
    int8 + ``(K,)`` scales -> the ``(L,)`` f32 sum (or mean) — bit-
    identical to ``decode_stacked_ref('int8', ...)``."""
    return _decode_reduce("int8", q, scales, length, mean, interpret)


def decode_reduce_int4(packed: jax.Array, scales: jax.Array, length: int,
                       *, mean: bool = True,
                       interpret: bool | None = None) -> jax.Array:
    """Fused decode+reduce of an all-gathered packed-int4 payload:
    ``(K, ceil(L/2))`` uint8 + ``(K,)`` scales -> the ``(L,)`` f32 sum
    (or mean) — bit-identical to ``decode_stacked_ref('int4', ...)``."""
    return _decode_reduce("int4", packed, scales, length, mean, interpret)


def decode_reduce_int2(packed: jax.Array, scales: jax.Array, length: int,
                       *, mean: bool = True,
                       interpret: bool | None = None) -> jax.Array:
    """Fused decode+reduce of an all-gathered packed-int2 payload:
    ``(K, ceil(L/4))`` uint8 + ``(K,)`` scales -> the ``(L,)`` f32 sum
    (or mean) — bit-identical to ``decode_stacked_ref('int2', ...)``."""
    return _decode_reduce("int2", packed, scales, length, mean, interpret)


# codec-name dispatch used by repro.comm.codec's on-TPU path
DECODE_REDUCE = {
    "int8": decode_reduce_int8,
    "int4": decode_reduce_int4,
    "int2": decode_reduce_int2,
}


def decode_mean_int8(q, scales, length, *, interpret=None):
    """``decode_reduce_int8(..., mean=True)`` — the bench-cell entry."""
    return decode_reduce_int8(q, scales, length, mean=True,
                              interpret=interpret)


def decode_mean_int4(packed, scales, length, *, interpret=None):
    """``decode_reduce_int4(..., mean=True)`` — the bench-cell entry."""
    return decode_reduce_int4(packed, scales, length, mean=True,
                              interpret=interpret)


def decode_mean_int2(packed, scales, length, *, interpret=None):
    """``decode_reduce_int2(..., mean=True)`` — the bench-cell entry."""
    return decode_reduce_int2(packed, scales, length, mean=True,
                              interpret=interpret)
