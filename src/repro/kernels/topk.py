"""Pallas TPU kernel: fused top-k magnitude select for the ``topk``
codec's encode (the PR 9 follow-up — ``lax.top_k`` was the only codec
without a fused encode path).

The jnp oracle (``TopKCodec.encode_ref``) sorts all L magnitudes to
keep k of them. This kernel instead runs argmax+mask sweeps — O(k*L)
VPU work with no sort network and k << L by construction (the codec
keeps ~1% of the entries). Selection is EXACT, so the outputs are
bit-identical to the oracle:

  * magnitudes are compared as ``jnp.abs`` of the same f32 input;
  * ties break to the lowest index (the first-occurrence argmax below
    matches ``lax.top_k``'s stable ordering);
  * selected values are read out exactly (a masked max against -inf,
    not an arithmetic reduction that could re-round);
  * the threshold is the k-th (last-selected) magnitude, the same
    ``mags[k-1]`` the oracle ships.

The update streams through VMEM as lane-dense ``(rows, 128)`` blocks
(``repro.kernels.tiling``), one grid step per block, so VMEM use does
not grow with L. The outputs stay resident across the grid and hold the
running top k, sorted: each step merges them with the new block by k
sweeps over (kept entries, block). Every kept index is lower than every
index in the block, so a tie between the two goes to the kept entry —
the global lowest-index rule. Padded lanes carry magnitude -1 so they
can never be selected (real magnitudes are >= 0); consumed lanes and
still-empty slots are masked the same way. The threshold leaves as a
broadcast (1, 128) lane row (a scalar cannot be stored to VMEM). The
wrapper runs compiled on TPU and in interpret mode everywhere else —
the same convention as ``quantize_pack_*``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE, row_tiles, to_rows
from repro.utils import compat


def _flat_index(shape) -> jax.Array:
    """Element index of each position of a lane-dense (rows, 128) tile."""
    return (lax.broadcasted_iota(jnp.int32, shape, 0) * LANE
            + lax.broadcasted_iota(jnp.int32, shape, 1))


def _topk_kernel(k: int, L: int, x_ref, v_ref, i_ref, t_ref, kept_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        v_ref[...] = jnp.zeros(v_ref.shape, jnp.float32)
        i_ref[...] = jnp.zeros(i_ref.shape, jnp.int32)
        kept_ref[...] = jnp.full(kept_ref.shape, -1.0, jnp.float32)

    x = x_ref[...]                                       # (rb, 128)
    lane = step * x.size + _flat_index(x.shape)          # global index
    mags = jnp.where(lane < L, jnp.abs(x), -1.0)
    kv, ki = v_ref[...], i_ref[...]                      # running top k
    slot = _flat_index(kv.shape)

    def body(i, carry):
        mags, kept, vals, idxs, kept_out, _ = carry
        m = jnp.maximum(jnp.max(kept), jnp.max(mags))
        # lowest index holding the max; kept indices precede the block's
        k_sel = jnp.min(jnp.where(kept == m, ki, L))
        b_sel = jnp.min(jnp.where(mags == m, lane, L))
        from_kept = k_sel < b_sel
        sel = jnp.where(from_kept, k_sel, b_sel)
        hit_kept = from_kept & (ki == sel) & (kept == m)
        hit_block = jnp.logical_not(from_kept) & (lane == sel)
        v = jnp.maximum(jnp.max(jnp.where(hit_kept, kv, -jnp.inf)),
                        jnp.max(jnp.where(hit_block, x, -jnp.inf)))
        vals = jnp.where(slot == i, v, vals)
        idxs = jnp.where(slot == i, sel, idxs)
        kept_out = jnp.where(slot == i, m, kept_out)
        kept = jnp.where(hit_kept, -1.0, kept)           # consume
        mags = jnp.where(hit_block, -1.0, mags)
        return mags, kept, vals, idxs, kept_out, m

    empty = jnp.full(kv.shape, -1.0, jnp.float32)
    _, _, vals, idxs, kept, thr = lax.fori_loop(
        0, k, body, (mags, kept_ref[...], kv, ki, empty, jnp.float32(0.0)))
    v_ref[...] = vals
    i_ref[...] = idxs
    kept_ref[...] = kept
    t_ref[...] = jnp.full(t_ref.shape, thr)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(dv: jax.Array, k: int, *, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k magnitude select of a 1-D f32 update: ``(values (k,) f32,
    indices (k,) int32, threshold f32)``, bit-identical to
    ``TopKCodec.encode_ref``."""
    L = dv.shape[0]
    assert 1 <= k <= L, (k, L)
    block, rows = row_tiles(L)
    k_rows = -(-k // LANE)
    resident = pl.BlockSpec((k_rows, LANE), lambda i: (0, 0))
    vals, idxs, thr = pl.pallas_call(
        functools.partial(_topk_kernel, k, L),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, LANE), lambda i: (i, 0))],
        out_specs=[resident, resident,
                   pl.BlockSpec((1, LANE), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((k_rows, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((k_rows, LANE), jnp.int32),
                   jax.ShapeDtypeStruct((1, LANE), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k_rows, LANE), jnp.float32)],
        interpret=compat.default_interpret(interpret),
        name="topk_select",
    )(to_rows(dv.astype(jnp.float32), rows))
    return vals.reshape(-1)[:k], idxs.reshape(-1)[:k], thr[0, 0]
