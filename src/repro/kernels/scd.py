"""Pallas TPU kernel for the CoCoA local SCD solver.

This is the TPU-native analogue of the paper's "offload the hot loop to
an optimized C++ module": the H sequential coordinate-descent steps run
entirely out of VMEM, with the per-step column data streamed
HBM -> VMEM by the Pallas pipeline.

TPU adaptation (vs the CPU/C++ original):
  * SCD gathers one column c_j per step. Random-access gathers from HBM
    inside a TPU kernel would serialize on DMA latency, so the caller
    pre-gathers the H visited columns into a dense (H, m) matrix with a
    single XLA gather; the kernel then *streams* that matrix through
    VMEM in (h_blk, S, m_blk) tiles via BlockSpec — sequential-friendly
    DMA, double-buffered by the Pallas pipeline.
  * The m dimension is LANE-TILED: rho and each streamed column live as
    (S, m_blk) = (ceil(m/128), 128) 2-D tiles instead of a single
    (1, m) row. A (1, m) row occupies one sublane of every (8, 128)
    f32 register tile — 7/8 of the VPU issue width wasted; the (S, 128)
    layout packs m across sublanes so the per-step dot and the rho
    update run at full width. rho is the kernel's resident VMEM f32
    accumulator (constant index_map), exactly the paper's "persistent
    local memory" idea pushed down the memory hierarchy.
  * The per-step scalars — the coordinate index, sigma*||c_j||^2,
    1/denom and the soft-threshold level lam_l1/denom — are precomputed
    VECTORIZED outside the kernel and prefetched whole into SMEM, so
    the serial H-step loop carries no divides, only mul/add and the
    reduction. The alpha block lives in SMEM too: the dynamically
    indexed ``alpha[j]`` read and store are scalar-unit work, and the
    TPU cannot store a scalar to VMEM. SMEM therefore holds
    4 * H + 2 * n_local words.
  * ``h_blk`` is picked from a VMEM budget (``_auto_h_blk``) when not
    given: the double-buffered column stream is the dominant tenant, so
    h_blk ~ budget / (2 * S * 128 * 4), clamped to [8, 512].
  * H is padded to a multiple of h_blk with csq = 0 tail steps — exact
    no-ops by construction (the ``scsq > 0`` guard restores alpha and
    the zero column leaves rho untouched), replacing the former hard
    ``H % h_blk == 0`` requirement.

The grid is sequential on TPU, which the state carried in VMEM and
SMEM relies on. Runs compiled on TPU and in interpret mode everywhere
else (same ``compat.default_interpret`` convention as the
quantize/decode kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANE as _LANE
from repro.utils import compat

_VMEM_BUDGET = 4 * 1024 * 1024  # bytes allotted to the column stream


def _auto_h_blk(S: int) -> int:
    """Steps per grid block from the VMEM budget: the double-buffered
    f32 column stream (2 * h_blk * S * 128 * 4 bytes) is the dominant
    tenant; clamp to [8, 512] and round down to a sublane multiple."""
    h = _VMEM_BUDGET // (2 * S * _LANE * 4)
    return max(8, min(512, (h // 8) * 8))


def _scd_kernel(sigma: float, h_blk: int, idx_ref, scsq_ref, dinv_ref,
                thr_ref, cols_ref, alpha_in_ref, w_ref, alpha_ref, rho_ref):
    """One grid step: h_blk sequential SCD updates on the kernel state.

    The per-step scalars and the alpha coordinates live in SMEM (scalar
    reads and the dynamically indexed ``alpha[j]`` store run on the
    scalar unit); the column stream and the rho accumulator are
    (S, 128) VMEM tiles."""
    i = pl.program_id(0)
    base = i * h_blk

    @pl.when(i == 0)
    def _init():
        def copy(j, _):
            alpha_ref[j] = alpha_in_ref[j]
            return 0
        lax.fori_loop(0, alpha_ref.shape[0], copy, 0)
        rho_ref[...] = w_ref[...].astype(jnp.float32)

    def body(s, _):
        j = idx_ref[base + s]
        c = cols_ref[s].astype(jnp.float32)             # (S, m_blk)
        scsq = scsq_ref[base + s]                       # sigma*||c_j||^2
        a = alpha_ref[j]
        rho = rho_ref[...]                              # (S, m_blk)
        z_tilde = (scsq * a - jnp.sum(rho * c)) * dinv_ref[base + s]
        z = jnp.sign(z_tilde) * jnp.maximum(
            jnp.abs(z_tilde) - thr_ref[base + s], 0.0)
        z = jnp.where(scsq > 0, z, a)                   # padded/zero col
        alpha_ref[j] = z
        rho_ref[...] = rho + (sigma * (z - a)) * c
        return 0

    lax.fori_loop(0, h_blk, body, 0)


@functools.partial(jax.jit, static_argnames=("sigma", "lam_eta", "lam_l1",
                                             "h_blk", "interpret"))
def scd_pallas(cols: jax.Array, csq: jax.Array, idx: jax.Array,
               alpha: jax.Array, w: jax.Array, *, sigma: float,
               lam_eta: float, lam_l1: float, h_blk: int | None = None,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Run H = cols.shape[0] SCD steps (any H >= 1; the tail is padded
    with exact no-op steps).

    Args:
      cols:  (H, m) pre-gathered columns, streaming dtype (f32/bf16).
      csq:   (H,) squared norms of the gathered columns.
      idx:   (H,) int32 local coordinate index per step.
      alpha: (n_local,) f32 local coordinates.
      w:     (m,) round-start shared residual, f32.
      h_blk: steps per grid block; ``None`` picks it from the VMEM
             budget via ``_auto_h_blk``.
    Returns:
      (alpha_new (n_local,) f32, rho (m,) f32).
    """
    interpret = compat.default_interpret(interpret)
    H, m = cols.shape
    assert H >= 1, H
    n_local = alpha.shape[0]
    S = -(-m // _LANE)
    mp = S * _LANE
    if h_blk is None:
        h_blk = _auto_h_blk(S)
    h_blk = max(1, min(h_blk, -(-H // 8) * 8))
    Hp = -(-H // h_blk) * h_blk

    cols_p = jnp.pad(cols, ((0, Hp - H), (0, mp - m)))
    cols3 = cols_p.reshape(Hp, S, _LANE)
    idx_p = jnp.pad(idx.astype(jnp.int32), (0, Hp - H))
    csq_p = jnp.pad(csq.astype(jnp.float32), (0, Hp - H))
    # per-step scalars, vectorized out of the serial loop: the kernel
    # body carries no divides (padded steps hit denom = lam_eta, which
    # is 0 for pure-l1 problems -> inf/NaN, discarded by the scsq > 0
    # guard exactly like the zero-column case)
    scsq = jnp.float32(sigma) * csq_p
    dinv = 1.0 / (scsq + jnp.float32(lam_eta))
    thr = jnp.float32(lam_l1) * dinv
    w3 = jnp.pad(w.astype(jnp.float32), (0, mp - m)).reshape(S, _LANE)

    kernel = functools.partial(_scd_kernel, float(sigma), h_blk)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    alpha_out, rho = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # idx, sigma*csq, 1/denom, threshold: whole (Hp,) in SMEM
            num_scalar_prefetch=4,
            grid=(Hp // h_blk,),
            in_specs=[
                pl.BlockSpec((h_blk, S, _LANE),
                             lambda i, *_: (i, 0, 0)),    # cols stream
                smem,                                     # alpha in
                pl.BlockSpec((S, _LANE), lambda i, *_: (0, 0)),  # w
            ],
            out_specs=[
                smem,                                     # alpha out
                pl.BlockSpec((S, _LANE), lambda i, *_: (0, 0)),  # rho
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_local,), jnp.float32),
            jax.ShapeDtypeStruct((S, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="scd",
    )(idx_p, scsq, dinv, thr, cols3, alpha.astype(jnp.float32), w3)
    return alpha_out, rho.reshape(mp)[:m]
