"""Pallas TPU kernels: quantize + pack for the ``compressed`` exchange's
wire codecs.

The jnp encode path (``repro.comm.codec``, the oracle) lowers to a
chain of HBM-materialized f32 intermediates — the scaled vector, the
rounded vector, the clipped vector — before the cast and (for int4 and
int2) the pack. These kernels do scale, round, clip, bias and pack in
one streaming pass over lane-dense ``(rows, 128)`` tiles
(``repro.kernels.tiling``), a grid step per block of rows:

  * int8: (1, R, 128) f32 in -> (R, 128) int8 out.
  * int4: (2, R, 128) f32 in (the codec's split-half pairing: element
    i pairs with element i + L/2, so "pack" is an elementwise
    ``lo | hi << 4`` of the two planes — no strided gathers) ->
    (R, 128) uint8 out.
  * int2: (4, R, 128) f32 in (split-quarter pairing) -> (R, 128)
    uint8 out, an elementwise two-bit shift-or of the four planes.

The absmax scale needs the whole vector before any element can be
quantized, so the wrapper computes it first with the codec's own
``scale`` (one reduction pass) and hands it to the kernel as a
broadcast (1, 128) lane row — a VMEM block, so the kernels batch under
``vmap`` (the virtual driver encodes all K workers at once); padded
tail elements quantize to the zero code and are sliced off. The
wrappers run compiled on TPU and in interpret mode everywhere else —
the same convention as ``scd_pallas`` — and are bit-identical to the
codec's ``encode_ref`` oracle (pinned by tests and the ``kernels``
benchmark).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.comm.codec import CODECS, INT2_QMAX, INT4_QMAX, INT8_QMAX
from repro.kernels.tiling import LANE, row_tiles, to_rows
from repro.utils import compat


def _quant_int8_kernel(s_ref, x_ref, q_ref):
    q_ref[...] = jnp.clip(jnp.round(x_ref[0] / s_ref[...]), -INT8_QMAX,
                          INT8_QMAX).astype(jnp.int8)


def _quant_int4_kernel(s_ref, x_ref, p_ref):
    x = x_ref[...]                                   # (2, rb, 128)
    q = jnp.clip(jnp.round(x / s_ref[...]), -INT4_QMAX,
                 INT4_QMAX).astype(jnp.int32) + 8    # biased nibbles
    p_ref[...] = (q[0] | (q[1] << 4)).astype(jnp.uint8)


def _quant_int2_kernel(s_ref, x_ref, p_ref):
    x = x_ref[...]                                   # (4, rb, 128)
    q = jnp.clip(jnp.round(x / s_ref[...]), -INT2_QMAX,
                 INT2_QMAX).astype(jnp.int32) + 2    # biased 2-bit codes
    p_ref[...] = (q[0] | (q[1] << 2) | (q[2] << 4)
                  | (q[3] << 6)).astype(jnp.uint8)


def _quantize(kernel, name: str, planes: jax.Array, scale: jax.Array,
              out_dtype, interpret: bool) -> jax.Array:
    """Run one quantize kernel over ``planes`` ``(P, n)`` f32 (P = 1,
    2 or 4 paired planes): returns the ``(n,)`` packed payload."""
    P, n = planes.shape
    block, rows = row_tiles(n)
    out = pl.pallas_call(
        kernel,
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((1, LANE), lambda i: (0, 0)),   # scale
                  pl.BlockSpec((P, block, LANE), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((block, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), out_dtype),
        interpret=interpret,
        name=name,
    )(jnp.broadcast_to(scale, (1, LANE)), to_rows(planes, rows))
    return out.reshape(rows * LANE)[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pack_int8(dv: jax.Array, *, interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused int8 encode of a 1-D f32 update: ``(q (L,) int8, scale)``,
    bit-identical to ``Int8Codec.encode_ref``."""
    dv = dv.astype(jnp.float32)
    scale = CODECS["int8"].scale(dv)
    q = _quantize(_quant_int8_kernel, "quantize_pack_int8", dv[None],
                  scale, jnp.int8, compat.default_interpret(interpret))
    return q, scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pack_int4(dv: jax.Array, *, interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused int4 encode of a 1-D f32 update: ``(packed (ceil(L/2),)
    uint8, scale)``, bit-identical to ``Int4Codec.encode_ref``."""
    L = dv.shape[0]
    half = -(-L // 2)
    dv = dv.astype(jnp.float32)
    scale = CODECS["int4"].scale(dv)
    planes = jnp.pad(dv, (0, 2 * half - L)).reshape(2, half)
    packed = _quantize(_quant_int4_kernel, "quantize_pack_int4", planes,
                       scale, jnp.uint8, compat.default_interpret(interpret))
    return packed, scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pack_int2(dv: jax.Array, *, interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Fused int2 encode of a 1-D f32 update: ``(packed (ceil(L/4),)
    uint8, scale)``, bit-identical to ``Int2Codec.encode_ref``."""
    L = dv.shape[0]
    quarter = -(-L // 4)
    dv = dv.astype(jnp.float32)
    scale = CODECS["int2"].scale(dv)
    planes = jnp.pad(dv, (0, 4 * quarter - L)).reshape(4, quarter)
    packed = _quantize(_quant_int2_kernel, "quantize_pack_int2", planes,
                       scale, jnp.uint8, compat.default_interpret(interpret))
    return packed, scale
