"""Update codecs: what one worker's update vector looks like on the wire.

An :class:`UpdateCodec` turns a 1-D f32 update into a tuple of *wire
arrays* (``encode``), reconstructs the f32 vector from a stacked
``(K, ...)`` gather of those arrays (``decode_stacked``), and prices the
per-worker payload (``wire_bytes``). The comm schemes in
``core/distributed.py`` all-gather every wire array and sum the decoded
stack — both the vmap virtual driver and the shard_map sharded driver
call the ONE codec object, so the two execution paths cannot drift.

Base codecs:

  * ``f32``  — identity: the update travels as-is (4 bytes/element).
    No scale array, so the wire tuple is just ``(dv,)`` and the HLO
    shows a single f32 all-gather.
  * ``int8`` — absmax quantization to [-127, 127] with one f32 scale
    per worker (1 byte/element + 4). This is the quantizer that used to
    live in ``core/distributed.py`` verbatim: for any nonzero input the
    encode/decode bits are identical to the pre-codec ``compressed``
    scheme (pinned by a regression test).
  * ``int4`` — absmax quantization to [-7, 7] packed two elements per
    byte (0.5 bytes/element + 4). The grid has 15 levels across
    [-absmax, absmax] (``scale = absmax / 7.5``, i.e. steps of
    2*absmax/15), so the round-trip error bound is ``scale / 2`` —
    about 8.5x the int8 codec's scale. Packing pairs element ``i`` with
    element ``i + ceil(L/2)`` (split-half pairing): pack and unpack are
    then pure elementwise nibble ops on two contiguous halves, with no
    strided gathers — the layout a TPU kernel can fuse.
  * ``int2`` — absmax ternary quantization to {-1, 0, 1} packed four
    elements per byte (0.25 bytes/element + 4). ``scale = absmax/1.5``
    (steps of 2*absmax/3) gives the same tight ``scale / 2`` round-trip
    bound as int4 by the same clip-at-the-extreme argument. Packing
    uses split-quarter pairing — element ``i`` with ``i + q``,
    ``i + 2q``, ``i + 3q`` for ``q = ceil(L/4)`` — so pack/unpack are
    elementwise two-bit shifts on four contiguous rows.
  * ``topk(r=..)`` — magnitude sparsification: ship the
    ``k = max(1, ceil(r * L))`` largest-magnitude entries as a
    ``(values f32, indices int32)`` pair plus one f32 threshold (the
    k-th largest magnitude — it bounds the per-element truncation
    error), ``4 * ceil(r*L) * 2 + 4`` wire bytes. The values stay f32
    on the wire: the compression is in WHICH entries ship, not their
    precision, so the wire-dtype lint expects no quantized dtypes here.

The ``ef:<base>`` wrapper adds *error feedback* (1-bit SGD, Seide et
al. 2014; EF-SGD, Karimireddy et al. 2019): it encodes
``dv + residual`` with the lossy base codec and keeps the quantization
error ``(dv + residual) - decode(encode(dv + residual))`` as per-worker
codec *state*, so every bit the grid rounds away this round re-enters
the sum next round. Biased codecs (int4's clipped extremes, top-k's
dropped tail) stop accumulating a systematic floor — the error is
delayed, not destroyed. Stateful codecs are the reason the drivers in
``core/distributed.py`` thread a codec-state slot alongside the local
state; history-free codecs carry a zero-length placeholder instead
(``StatelessCodec``).

Zero is a guaranteed fixed point of every codec: the quantized grids
are symmetric and contain 0, and the scale is explicitly guarded
(``scale = 1`` when ``absmax == 0``) so an all-zero update decodes to
exact zeros by construction, not by luck of ``0 / eps`` rounding. The
elastic ``drop:`` regime leans on this — a dropped worker's zeroed
update (and zeroed residual) contributes exact zeros through any codec.

On TPU the int8/int4/int2 ``encode`` dispatches to the fused Pallas
quantize+pack kernel (``repro.kernels.quant``) so absmax-scale, round,
clip and pack happen in one VMEM pass instead of materializing f32
intermediates in HBM; everywhere else it runs the jnp path below, which
doubles as the kernel's bit-exact oracle. The gather side is fused the
same way: ``decode_stacked_sum`` / ``decode_stacked_mean`` reduce the
all-gathered ``(K, wire)`` payload worker-by-worker — on TPU through
the fused Pallas decode+reduce kernels (``repro.kernels.dequant``),
elsewhere through the sequential-accumulation oracle — so the exchange
never materializes the ``(K, L)`` f32 stack the ``f32-intermediate``
lint rule (error severity) forbids. The reduction order is the
SEQUENTIAL canonical worker order (k = 0..K-1, mean = sum times the
f32-rounded 1/K) on both paths, which replaced the pre-PR-10
``jnp.sum(stack, axis=0)`` — same math, deterministic ulp-level
difference in the aggregate. ``topk`` encode likewise dispatches to the
fused argmax+mask select kernel (``repro.kernels.topk``) on TPU.
"""
from __future__ import annotations

import functools
import math
import re
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.utils import compat

FP_ITEMSIZE = 4        # every dense array in the system is float32
SCALE_BYTES = 4        # one f32 absmax scale per worker per round

INT8_QMAX = 127.0      # int8 grid: 255 levels across [-absmax, absmax]
INT4_QMAX = 7.0        # int4 grid: 15 levels (q in [-7, 7]; -8 unused
#                        so the grid stays symmetric and contains 0)
INT4_SCALE_DIV = 7.5   # scale = absmax/7.5 -> steps of 2*absmax/15;
#                        interior elements round to within scale/2, and
#                        the absmax element itself sits at dv/scale =
#                        7.5 exactly — round-half-even takes it to 8,
#                        the clip pulls it back to 7, and the resulting
#                        error is |7.5-7|*scale = scale/2: the clip DOES
#                        bite there, landing exactly on the bound, so
#                        the round-trip error is <= scale/2 everywhere
#                        (tight at the extreme, not slack)
INT2_QMAX = 1.0        # int2 grid: 3 levels {-1, 0, 1} (biased 2-bit
#                        codes land in [1, 3]; 0 unused, same symmetry
#                        argument as int4's unused -8)
INT2_SCALE_MUL = 2.0 / 3.0  # scale = absmax * 2/3 (i.e. absmax/1.5 ->
#                        steps of 2*absmax/3): the absmax element sits
#                        at dv/scale ~= 1.5, rounds to 2, the clip pulls
#                        it back to 1, error = scale/2 — the identical
#                        tight-at-the-extreme bound as int4. Expressed
#                        as a MULTIPLY (not /1.5) because XLA strength-
#                        reduces division by 1.5 inconsistently between
#                        the jnp oracle and Pallas interpret mode — one
#                        ulp apart — while a multiply by the f32-rounded
#                        2/3 is the same op on both paths

TOPK_DEFAULT_R = 0.01  # bare "topk" keeps 1% of the entries


@runtime_checkable
class UpdateCodec(Protocol):
    """What a codec plugs into the comm schemes and the byte model.

    ``encode``         one worker's 1-D f32 update -> tuple of wire
                       arrays (payload first; a per-worker f32 scale
                       follows when the codec has one — by convention
                       the scale is always the LAST wire part).
    ``decode``         the wire tuple of ONE worker -> the f32 vector.
    ``decode_stacked`` the all-gathered ``(K, ...)`` wire tuple -> the
                       ``(K, L)`` f32 stack (diagnostic/test surface).
    ``decode_stacked_sum`` / ``decode_stacked_mean``
                       the all-gathered wire tuple -> the ``(L,)``
                       reduced aggregate directly — the call the
                       exchanges make, fused on TPU so no ``(K, L)``
                       f32 stack is ever materialized.
    ``wire_bytes``     per-worker payload bytes for a length-L update —
                       the number the byte model charges and the
                       ``drivers`` benchmark checks against the HLO.

    Stateful codecs (``stateful = True``) additionally carry a
    per-worker state vector between rounds: ``init_state(L)`` is the
    round-0 carry and ``encode_with_state`` returns
    ``(wire parts, new state)``. Stateless codecs expose the same
    surface with a zero-length placeholder so driver plumbing never
    branches on codec identity at trace time.

    ``lossless`` marks codecs whose round-trip is exact (only ``f32``):
    the delta-only check in ``optim/local_updates.py`` and the
    ``ef:`` wrapper's no-error-to-feed-back guard both key off it
    instead of string-matching names.
    """
    name: str
    stateful: bool
    lossless: bool

    def encode(self, dv: jax.Array) -> tuple[jax.Array, ...]: ...

    def decode(self, parts, length: int) -> jax.Array: ...

    def decode_stacked(self, parts, length: int) -> jax.Array: ...

    def decode_stacked_sum(self, parts, length: int) -> jax.Array: ...

    def decode_stacked_mean(self, parts, length: int) -> jax.Array: ...

    def wire_bytes(self, length: int) -> int: ...

    def init_state(self, length: int) -> jax.Array: ...

    def encode_with_state(self, dv: jax.Array, state: jax.Array
                          ) -> tuple[tuple[jax.Array, ...], jax.Array]: ...


def _absmax_scale(dv: jax.Array, div: float, eps: float) -> jax.Array:
    """Per-vector absmax scale with the explicit zero guard: an all-zero
    input gets scale 1 (any finite value works — q is 0 everywhere), so
    ``decode(encode(0)) == 0`` exactly instead of relying on ``0 / eps``
    rounding to zero."""
    absmax = jnp.max(jnp.abs(dv))
    return jnp.where(absmax > 0, absmax / div + eps, 1.0)


def _split_halves(dv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) halves of the zero-padded-to-even vector: element ``i``
    pairs with element ``i + ceil(L/2)``."""
    L = dv.shape[0]
    half = -(-L // 2)
    dv = jnp.concatenate([dv, jnp.zeros((2 * half - L,), dv.dtype)])
    return dv[:half], dv[half:]


def _split_quarters(dv: jax.Array) -> jax.Array:
    """(4, ceil(L/4)) rows of the zero-padded vector: element ``i``
    pairs with ``i + q``, ``i + 2q``, ``i + 3q`` (split-quarter
    pairing), the two-bit analogue of ``_split_halves``."""
    L = dv.shape[0]
    quarter = -(-L // 4)
    dv = jnp.concatenate([dv, jnp.zeros((4 * quarter - L,), dv.dtype)])
    return dv.reshape(4, quarter)


class StatelessCodec:
    """Base for history-free codecs: the per-worker codec state is a
    zero-length placeholder and ``encode_with_state`` is ``encode`` —
    the drivers thread ONE surface regardless of codec identity.

    The base ``decode_stacked_sum`` / ``decode_stacked_mean`` reduce
    the decoded stack with ``jnp.sum`` / ``jnp.mean`` — fine for codecs
    whose stack is already f32 wire data (``f32``) or sparse scatters
    (``topk``); the quantized codecs override with the fused
    sequential-accumulation path (``_QuantFusedReduce``)."""
    stateful = False
    lossless = False

    def init_state(self, length: int) -> jax.Array:
        del length
        return jnp.zeros((0,), jnp.float32)

    def encode_with_state(self, dv: jax.Array, state: jax.Array):
        return self.encode(dv), state

    def decode_stacked_sum(self, parts, length: int) -> jax.Array:
        return jnp.sum(self.decode_stacked(parts, length), axis=0)

    def decode_stacked_mean(self, parts, length: int) -> jax.Array:
        return jnp.mean(self.decode_stacked(parts, length), axis=0)


class _QuantFusedReduce:
    """Fused decode+reduce for the quantized codecs (int8/int4/int2).

    ``decode_reduce_ref`` is the jnp oracle the Pallas kernels in
    ``repro.kernels.dequant`` are bit-identical to: decode one worker's
    row at a time and accumulate SEQUENTIALLY in canonical worker order
    — the only f32 intermediates are ``(L,)``-sized, K times smaller
    than the ``(K, L)`` stack the ``f32-intermediate`` lint rule (error
    severity) forbids, so the off-TPU sweep in ``repro.analysis`` is
    clean by the same construction that makes the TPU path fast. The
    mean is the sum times the f32-rounded ``1/K`` (bit-equal to
    ``jnp.mean`` would not survive the fused accumulation; the kernels
    and this oracle agree with EACH OTHER, which is the contract)."""

    def decode_reduce_ref(self, parts, length: int, *, mean: bool
                          ) -> jax.Array:
        from repro.kernels.dequant import _no_fma
        payload, scales = parts              # (K, wire), (K,)
        K = payload.shape[0]
        # _no_fma walls the decoded row (a q*scale product) off from
        # the accumulate add — without it the backend may FMA-contract
        # ``acc + q*scale`` on one compilation but not another, a 1-ulp
        # drift that breaks the kernel/oracle bit-identity contract.
        acc = _no_fma(self.decode((payload[0], scales[0]), length))
        for k in range(1, K):
            acc = acc + _no_fma(
                self.decode((payload[k], scales[k]), length))
        return acc * (1.0 / K) if mean else acc

    def _decode_reduce(self, parts, length: int, *, mean: bool
                       ) -> jax.Array:
        if compat.on_tpu():
            from repro.kernels.dequant import DECODE_REDUCE
            return DECODE_REDUCE[self.name](parts[0], parts[1], length,
                                            mean=mean)
        return self.decode_reduce_ref(parts, length, mean=mean)

    def decode_stacked_sum(self, parts, length: int) -> jax.Array:
        return self._decode_reduce(parts, length, mean=False)

    def decode_stacked_mean(self, parts, length: int) -> jax.Array:
        return self._decode_reduce(parts, length, mean=True)


class F32Codec(StatelessCodec):
    """Identity codec: the f32 update IS the wire format."""
    name = "f32"
    lossless = True

    def encode(self, dv: jax.Array) -> tuple[jax.Array]:
        return (dv,)

    def decode(self, parts, length: int) -> jax.Array:
        return parts[0]

    def decode_stacked(self, parts, length: int) -> jax.Array:
        return parts[0]

    def wire_bytes(self, length: int) -> int:
        return length * FP_ITEMSIZE


class Int8Codec(_QuantFusedReduce, StatelessCodec):
    """Absmax int8 quantization with a per-worker f32 scale — byte-for-
    byte the quantizer the ``compressed`` scheme always used (the
    ``+ 1e-30`` term is kept so nonzero inputs quantize identically to
    the pre-codec implementation; the zero guard only changes the
    never-observable scale of an all-zero vector)."""
    name = "int8"

    def encode(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        if compat.on_tpu():
            from repro.kernels.quant import quantize_pack_int8
            return quantize_pack_int8(dv)
        return self.encode_ref(dv)

    def scale(self, dv: jax.Array) -> jax.Array:
        """The per-worker absmax scale (shared with the Pallas kernel)."""
        return _absmax_scale(dv, INT8_QMAX, 1e-30)

    def encode_ref(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The jnp path (and the Pallas kernel's bit-exact oracle)."""
        scale = self.scale(dv)
        q = jnp.clip(jnp.round(dv / scale), -INT8_QMAX,
                     INT8_QMAX).astype(jnp.int8)
        return q, scale

    def decode(self, parts, length: int) -> jax.Array:
        q, scale = parts
        return q.astype(jnp.float32) * scale

    def decode_stacked(self, parts, length: int) -> jax.Array:
        q, scale = parts                     # (K, L), (K,)
        return q.astype(jnp.float32) * scale[:, None]

    def wire_bytes(self, length: int) -> int:
        return length + SCALE_BYTES


class Int4Codec(_QuantFusedReduce, StatelessCodec):
    """Absmax int4 quantization, two elements per byte.

    ``q = clip(round(dv / scale), -7, 7)`` with ``scale = absmax/7.5``;
    nibbles are stored biased (``q + 8`` in [1, 15]) and packed
    ``lo | hi << 4`` under split-half pairing, so pack/unpack are
    elementwise on contiguous halves. Wire cost: ``ceil(L/2)`` payload
    bytes + the 4-byte scale.
    """
    name = "int4"

    def encode(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        if compat.on_tpu():
            from repro.kernels.quant import quantize_pack_int4
            return quantize_pack_int4(dv)
        return self.encode_ref(dv)

    def scale(self, dv: jax.Array) -> jax.Array:
        """The per-worker absmax scale (shared with the Pallas kernel)."""
        return _absmax_scale(dv, INT4_SCALE_DIV, 0.0)

    def encode_ref(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The jnp path (and the Pallas kernel's bit-exact oracle)."""
        scale = self.scale(dv)
        lo, hi = _split_halves(dv)
        qlo = jnp.clip(jnp.round(lo / scale), -INT4_QMAX,
                       INT4_QMAX).astype(jnp.int32) + 8
        qhi = jnp.clip(jnp.round(hi / scale), -INT4_QMAX,
                       INT4_QMAX).astype(jnp.int32) + 8
        return (qlo | (qhi << 4)).astype(jnp.uint8), scale

    def _unpack(self, packed: jax.Array, length: int) -> jax.Array:
        """(..., ceil(L/2)) packed bytes -> (..., L) f32-ready int grid
        values in [-7, 7] (the padded tail nibble is sliced off)."""
        p = packed.astype(jnp.int32)
        q = jnp.concatenate([p & 0xF, p >> 4], axis=-1) - 8
        return q[..., :length].astype(jnp.float32)

    def decode(self, parts, length: int) -> jax.Array:
        packed, scale = parts
        return self._unpack(packed, length) * scale

    def decode_stacked(self, parts, length: int) -> jax.Array:
        packed, scale = parts                # (K, L2), (K,)
        return self._unpack(packed, length) * scale[:, None]

    def wire_bytes(self, length: int) -> int:
        return -(-length // 2) + SCALE_BYTES


class Int2Codec(_QuantFusedReduce, StatelessCodec):
    """Absmax ternary quantization, four elements per byte.

    ``q = clip(round(dv / scale), -1, 1)`` with ``scale = absmax*2/3``;
    codes are stored biased (``q + 2`` in [1, 3]) and packed
    ``q0 | q1<<2 | q2<<4 | q3<<6`` under split-quarter pairing, so
    pack/unpack are elementwise two-bit shifts on four contiguous rows.
    Wire cost: ``ceil(L/4)`` payload bytes + the 4-byte scale. Alone
    the 3-level grid is far too coarse to converge — it exists for the
    ``ef:int2`` composition, where the residual carries what the grid
    cannot.
    """
    name = "int2"

    def encode(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        if compat.on_tpu():
            from repro.kernels.quant import quantize_pack_int2
            return quantize_pack_int2(dv)
        return self.encode_ref(dv)

    def scale(self, dv: jax.Array) -> jax.Array:
        """The per-worker absmax scale (shared with the Pallas kernel)."""
        absmax = jnp.max(jnp.abs(dv))
        return jnp.where(absmax > 0, absmax * INT2_SCALE_MUL, 1.0)

    def encode_ref(self, dv: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The jnp path (and the Pallas kernel's bit-exact oracle)."""
        scale = self.scale(dv)
        rows = _split_quarters(dv)
        q = jnp.clip(jnp.round(rows / scale), -INT2_QMAX,
                     INT2_QMAX).astype(jnp.int32) + 2
        packed = q[0] | (q[1] << 2) | (q[2] << 4) | (q[3] << 6)
        return packed.astype(jnp.uint8), scale

    def _unpack(self, packed: jax.Array, length: int) -> jax.Array:
        """(..., ceil(L/4)) packed bytes -> (..., L) f32-ready grid
        values in [-1, 1] (padded tail codes are sliced off)."""
        p = packed.astype(jnp.int32)
        q = jnp.concatenate([p & 0x3, (p >> 2) & 0x3, (p >> 4) & 0x3,
                             (p >> 6) & 0x3], axis=-1) - 2
        return q[..., :length].astype(jnp.float32)

    def decode(self, parts, length: int) -> jax.Array:
        packed, scale = parts
        return self._unpack(packed, length) * scale

    def decode_stacked(self, parts, length: int) -> jax.Array:
        packed, scale = parts                # (K, L4), (K,)
        return self._unpack(packed, length) * scale[:, None]

    def wire_bytes(self, length: int) -> int:
        return -(-length // 4) + SCALE_BYTES


class TopKCodec(StatelessCodec):
    """Magnitude sparsification: ship only the ``k = max(1, ceil(r*L))``
    largest-|.| entries.

    Wire tuple: ``(values f32 (k,), indices int32 (k,), threshold)``
    where the threshold — kept last like every codec's scale — is the
    k-th largest magnitude: shipped entries decode exactly, and every
    dropped entry's error is bounded by it. Wire cost:
    ``4 * ceil(r*L) * 2 + 4`` bytes (f32 value + int32 index per kept
    entry, plus the threshold). The values are legitimately f32 on the
    wire, so this codec has no ``CODEC_WIRE_DTYPE`` entry.
    """

    def __init__(self, r: float):
        self.r = float(r)
        self.name = f"topk(r={self.r:g})"

    def _k(self, length: int) -> int:
        return min(int(length), max(1, math.ceil(self.r * length)))

    def encode(self, dv: jax.Array) -> tuple[jax.Array, ...]:
        if compat.on_tpu():
            from repro.kernels.topk import topk_select
            return topk_select(dv, self._k(dv.shape[0]))
        return self.encode_ref(dv)

    def encode_ref(self, dv: jax.Array) -> tuple[jax.Array, ...]:
        """The jnp path (and the Pallas select kernel's bit-exact
        oracle): ``lax.top_k`` over the magnitudes."""
        k = self._k(dv.shape[0])
        mags, idx = jax.lax.top_k(jnp.abs(dv), k)
        return jnp.take(dv, idx), idx.astype(jnp.int32), mags[k - 1]

    def decode(self, parts, length: int) -> jax.Array:
        values, idx, thr = parts
        values = self._enforce(values, thr)
        return jnp.zeros((length,), jnp.float32).at[idx].set(values)

    def decode_stacked(self, parts, length: int) -> jax.Array:
        values, idx, thr = parts             # (K, k), (K, k), (K,)
        K = values.shape[0]
        values = self._enforce(values, thr[:, None])
        out = jnp.zeros((K, length), jnp.float32)
        return out.at[jnp.arange(K)[:, None], idx].set(values)

    @staticmethod
    def _enforce(values, thr):
        """Drop anything below the advertised threshold. Every honestly
        encoded value satisfies ``|v| >= thr`` (thr IS the k-th largest
        magnitude), so this is the identity on real wire data — but it
        makes decode actually CONSUME the threshold, so its all-gather
        is live payload instead of dead code XLA deletes (the byte
        model charges the threshold; the HLO must carry it)."""
        return jnp.where(jnp.abs(values) >= thr, values, 0.0)

    def wire_bytes(self, length: int) -> int:
        return 2 * FP_ITEMSIZE * self._k(length) + SCALE_BYTES


class EFWrapper:
    """Error feedback around a lossy base codec (``ef:<base>``).

    ``encode_with_state`` compresses ``dv + residual`` and returns the
    new residual ``(dv + residual) - decode(...)`` — the per-worker
    state the drivers carry between rounds. Everything the base grid
    rounds away (or top-k drops) re-enters the sum next round, which
    converts the base codec's bias into a bounded delay: this is what
    lifts the plain-int4 convergence floor. The plain ``encode`` entry
    point encodes with a zero residual, so stateless call sites (link
    calibration, codec-path tests) see exactly the base codec.
    """
    stateful = True
    lossless = False

    def __init__(self, base: UpdateCodec):
        self.base = base
        self.name = f"ef:{base.name}"

    def init_state(self, length: int) -> jax.Array:
        return jnp.zeros((length,), jnp.float32)

    def encode(self, dv: jax.Array) -> tuple[jax.Array, ...]:
        return self.base.encode(dv)

    def encode_ref(self, dv: jax.Array) -> tuple[jax.Array, ...]:
        return self.base.encode_ref(dv)

    def encode_with_state(self, dv: jax.Array, state: jax.Array):
        e = dv + state
        parts = self.base.encode(e)
        return parts, e - self.base.decode(parts, e.shape[0])

    def decode(self, parts, length: int) -> jax.Array:
        return self.base.decode(parts, length)

    def decode_stacked(self, parts, length: int) -> jax.Array:
        return self.base.decode_stacked(parts, length)

    def decode_stacked_sum(self, parts, length: int) -> jax.Array:
        return self.base.decode_stacked_sum(parts, length)

    def decode_stacked_mean(self, parts, length: int) -> jax.Array:
        return self.base.decode_stacked_mean(parts, length)

    def wire_bytes(self, length: int) -> int:
        return self.base.wire_bytes(length)


CODECS: dict[str, UpdateCodec] = {
    c.name: c for c in (F32Codec(), Int8Codec(), Int4Codec(), Int2Codec())
}

_TOPK_RE = re.compile(r"topk(?:\((?P<arg>[^)]*)\))?")


@functools.lru_cache(maxsize=None)
def get_codec(name: str) -> UpdateCodec:
    """Validated codec lookup (raises on typos instead of silently
    falling back to the identity). Cached, so every call site parsing
    the same spec shares ONE codec object — the vmap/shard_map identity
    contract extends to parameterized codecs like ``topk(r=..)``."""
    if name in CODECS:
        return CODECS[name]
    if name.startswith("ef:"):
        inner = name[len("ef:"):]
        if inner.startswith("ef:"):
            raise ValueError(
                f"bad codec {name!r}: error feedback does not nest — "
                f"one residual per worker; use a single 'ef:' prefix")
        base = get_codec(inner)
        if base.lossless:
            raise ValueError(
                f"bad codec {name!r}: {inner!r} round-trips exactly, so "
                f"there is no quantization error to feed back — drop "
                f"the 'ef:' prefix")
        return EFWrapper(base)
    m = _TOPK_RE.fullmatch(name)
    if m is not None:
        arg = m.group("arg")
        if not arg:
            r = TOPK_DEFAULT_R
        else:
            body = arg[2:] if arg.startswith("r=") else arg
            try:
                r = float(body)
            except ValueError:
                raise ValueError(
                    f"bad codec {name!r}: expected topk(r=<float>), "
                    f"got argument {arg!r}") from None
        if not 0.0 < r <= 1.0:
            raise ValueError(
                f"bad codec {name!r}: keep ratio r={r!r} must satisfy "
                f"0 < r <= 1")
        return TopKCodec(r)
    raise ValueError(
        f"unknown update codec {name!r}; known: {tuple(CODECS)} plus "
        f"'topk(r=<float>)' and the 'ef:<lossy base>' wrapper")
