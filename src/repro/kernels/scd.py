"""Pallas TPU kernel for the CoCoA local SCD solver.

This is the TPU-native analogue of the paper's "offload the hot loop to
an optimized C++ module": the H sequential coordinate-descent steps run
entirely out of VMEM, and the kernel fetches each visited column from
the worker's block in HBM itself.

TPU adaptation (vs the CPU/C++ original):
  * The worker's block is stored column-major and LANE-TILED:
    ``(n_local, S, 128)`` with S = ceil(m/128) (``partition.tile_columns``
    lays the stack out so). Column j is one contiguous, tile-aligned
    (S, 128) slab ``A[j]``, m zero-padded to S*128 rows; a zero row
    changes neither a dot product nor rho. rho and each column live as
    (S, 128) tiles instead of a single (1, m) row, which would occupy
    one sublane of every (8, 128) f32 register tile and waste 7/8 of
    the VPU issue width; rho is the kernel's resident VMEM f32
    accumulator, exactly the paper's "persistent local memory" idea
    pushed down the memory hierarchy.
  * Index-driven fetch. The coordinate stream is prefetched into SMEM,
    so the kernel knows every column it will visit: at step s it starts
    the DMA of column ``idx[s + NBUF - 1]`` into a ring of NBUF column
    buffers, then waits for column ``idx[s]`` and computes with it. Each
    fetch is one contiguous copy of 4*S*128 bytes, so it runs at HBM
    bandwidth and hides behind the steps before it. Only the H visited
    columns are read, and no (H, m) column matrix is built outside the
    kernel.
  * VMEM holds the column ring, NBUF tiles, plus w and rho, one tile
    each: (NBUF + 2) * 512 * S bytes whatever H is, 3.75 MiB at
    m = 196,608 and 7.6 MiB at m = 400,000.
  * The per-step scalars — the coordinate index, sigma*||c_j||^2,
    1/denom and the soft-threshold level lam_l1/denom — are precomputed
    VECTORIZED outside the kernel and prefetched whole into SMEM, so
    the serial H-step loop carries no divides, only mul/add and the
    reduction. The alpha block lives in SMEM too: the dynamically
    indexed ``alpha[j]`` read and store are scalar-unit work, and the
    TPU cannot store a scalar to VMEM. SMEM therefore holds
    4 * H + 2 * n_local words.
  * All H steps run in one kernel invocation, as two loops: the first
    H - (NBUF - 1) steps each start a fetch, the last NBUF - 1 have
    nothing left to fetch. Any H >= 1 works with no padded steps.

Runs compiled on TPU and in interpret mode everywhere else (same
``compat.default_interpret`` convention as the quantize/decode
kernels).
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

NBUF = 3  # column buffers in the fetch ring: NBUF - 1 fetches ahead


def _scd_kernel(sigma: float, idx_ref, scsq_ref, dinv_ref, thr_ref, A_hbm,
                alpha_in_ref, w_ref, alpha_ref, rho_ref, cols, sems):
    """All H = idx.shape[0] sequential SCD updates on the kernel state.

    The per-step scalars and the alpha coordinates live in SMEM (scalar
    reads and the dynamically indexed ``alpha[j]`` store run on the
    scalar unit); the fetched columns and the rho accumulator are
    (S, 128) VMEM tiles."""
    H = idx_ref.shape[0]

    def fetch(step):
        slot = step % NBUF
        return pltpu.make_async_copy(A_hbm.at[idx_ref[step]], cols.at[slot],
                                     sems.at[slot])

    for step in range(min(NBUF - 1, H)):
        fetch(step).start()

    def copy(j, _):
        alpha_ref[j] = alpha_in_ref[j]
        return 0
    lax.fori_loop(0, alpha_ref.shape[0], copy, 0)
    rho_ref[...] = w_ref[...]

    def body(prefetch, step, _):
        if prefetch:
            fetch(step + NBUF - 1).start()
        fetch(step).wait()
        j = idx_ref[step]
        c = cols[step % NBUF].astype(jnp.float32)      # (S, 128)
        scsq = scsq_ref[step]                           # sigma*||c_j||^2
        a = alpha_ref[j]
        rho = rho_ref[...]                              # (S, 128)
        z_tilde = (scsq * a - jnp.sum(rho * c)) * dinv_ref[step]
        z = jnp.sign(z_tilde) * jnp.maximum(
            jnp.abs(z_tilde) - thr_ref[step], 0.0)
        z = jnp.where(scsq > 0, z, a)                   # padded/zero col
        alpha_ref[j] = z
        rho_ref[...] = rho + (sigma * (z - a)) * c
        return 0

    # the steps whose column NBUF - 1 ahead exists prefetch it; the last
    # NBUF - 1 steps have nothing left to fetch
    n_ahead = max(H - (NBUF - 1), 0)
    lax.fori_loop(0, n_ahead, functools.partial(body, True), 0)
    lax.fori_loop(n_ahead, H, functools.partial(body, False), 0)


@functools.partial(jax.jit, static_argnames=("sigma", "lam_eta", "lam_l1",
                                             "interpret"))
def scd_pallas(A: jax.Array, csq: jax.Array, idx: jax.Array,
               alpha: jax.Array, w: jax.Array, *, sigma: float,
               lam_eta: float, lam_l1: float, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Run H = idx.shape[0] SCD steps (any H >= 1), fetching column
    ``A[idx[s]]`` at step s.

    Args:
      A:     (n_local, S, 128) the worker's lane-tiled column block,
             S = ceil(m / 128), rows past m zero.
      csq:   (H,) squared norms of the visited columns.
      idx:   (H,) int32 local coordinate index per step.
      alpha: (n_local,) f32 local coordinates.
      w:     (m,) round-start shared residual, f32.
    Returns:
      (alpha_new (n_local,) f32, rho (m,) f32).
    """
    interpret = compat.default_interpret(interpret)
    n_local, S, lane = A.shape
    (H,), m = idx.shape, w.shape[0]
    assert H >= 1, H
    assert lane == _LANE and S == -(-m // _LANE), (A.shape, m)
    assert alpha.shape == (n_local,), (alpha.shape, n_local)
    mp = S * _LANE

    # per-step scalars, vectorized out of the serial loop: the kernel
    # body carries no divides (a zero column hits denom = lam_eta, which
    # is 0 for pure-l1 problems -> inf/NaN, discarded by the scsq > 0
    # guard)
    scsq = jnp.float32(sigma) * csq.astype(jnp.float32)
    dinv = 1.0 / (scsq + jnp.float32(lam_eta))
    thr = jnp.float32(lam_l1) * dinv
    w3 = jnp.pad(w.astype(jnp.float32), (0, mp - m)).reshape(S, _LANE)

    kernel = functools.partial(_scd_kernel, float(sigma))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec(memory_space=pltpu.VMEM)          # whole, one copy
    alpha_out, rho = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # idx, sigma*csq, 1/denom, threshold: whole (H,) in SMEM
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),        # A, left in HBM
                smem,                                     # alpha in
                tile,                                     # w
            ],
            out_specs=[smem, tile],                       # alpha, rho
            scratch_shapes=[pltpu.VMEM((NBUF, S, _LANE), A.dtype),
                            pltpu.SemaphoreType.DMA((NBUF,))]),
        out_shape=[
            jax.ShapeDtypeStruct((n_local,), jnp.float32),
            jax.ShapeDtypeStruct((S, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="scd",
    )(idx.astype(jnp.int32), scsq, dinv, thr, A,
      alpha.astype(jnp.float32), w3)
    return alpha_out, rho.reshape(mp)[:m]
