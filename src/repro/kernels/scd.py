"""Pallas TPU kernel for the CoCoA local SCD solver.

This is the TPU-native analogue of the paper's "offload the hot loop to
an optimized C++ module": the H sequential coordinate-descent steps run
in one kernel invocation, and the kernel fetches each visited column
from the workers' stack in HBM itself, at its worker's row.

TPU adaptation (vs the CPU/C++ original):
  * The worker's block is stored column-major and LANE-TILED:
    ``(n_local, S, 128)`` with S = 8 * ceil(m/1024), whole (8, 128)
    tiles (``tiling.column_rows``; ``partition.pack_columns`` lays the
    stack out so). Column j is one contiguous, tile-aligned (S, 128)
    slab ``A[j]``, m zero-padded to S*128 rows; a zero row
    changes neither a dot product nor rho. rho and each column live as
    (S, 128) tiles instead of a single (1, m) row, which would occupy
    one sublane of every (8, 128) f32 register tile and waste 7/8 of
    the VPU issue width.
  * Index-driven fetch. The coordinate stream is prefetched into SMEM,
    so the kernel knows every column it will visit and reads only
    those, straight from the block: no (H, m) column matrix is built
    outside the kernel. The block is not cut out of the
    ``(K, n_local, S, 128)`` stack either: the kernel takes the whole
    stack, left in HBM, with the worker's index k as one more SMEM
    word, and fetches column j of worker k as ``A[k, j]``. A lone
    ``(n_local, S, 128)`` block is a stack of one, worker 0.
  * The per-step scalars — the coordinate index, sigma*||c_j||^2,
    1/denom and the soft-threshold level lam_l1/denom — are precomputed
    VECTORIZED outside the kernel and prefetched whole into SMEM, so
    the serial H-step loop carries no divides, only mul/add and the
    reduction. The alpha block lives in SMEM too: the dynamically
    indexed ``alpha[j]`` read and store are scalar-unit work, and the
    TPU cannot store a scalar to VMEM. SMEM therefore holds
    4 * H + 2 * n_local + 1 words, the last the worker's index.

Two forms, picked from S alone (``_tile_rows``; nobody sets it):

  * Resident, where NBUF whole columns, w and rho fit ``VMEM_BUDGET``
    ((NBUF + 2) * 512 * S bytes whatever H is: 3.75 MiB at m = 196,608,
    7.6 MiB at m = 400,000; up to m = 628,736). rho is the kernel's
    resident VMEM f32 accumulator, the paper's "persistent local
    memory" pushed down the memory hierarchy. At step s the kernel
    starts the DMA of column ``idx[s + NBUF - 1]`` into a ring of NBUF
    column buffers, then waits for column ``idx[s]`` and computes with
    it: the first H - (NBUF - 1) steps each start a fetch, the last
    NBUF - 1 have nothing left to fetch. Each fetch is one contiguous
    copy of 4*S*128 bytes and hides behind the steps before it.
  * Row-tiled, for taller columns (at m = 11,000,000 a column is
    44 MB). rho stays in HBM, and the kernel streams in tiles of T rows
    (T * 512 B each, 12 tiles within the budget: T = 2,048). A step
    needs the whole dot rho^T c before its axpy rho += delta c, so the
    steps run as H + 1 sweeps over the rows: sweep s applies step
    s - 1's axpy to each tile and accumulates step s's dot on the
    updated tile. Sweep 0 only reads w and c_{idx[0]}; sweep H only
    writes. A sweep reads rho (w in sweeps 0 and 1), the two columns
    and writes rho back, double-buffered tile by tile; the last
    S mod T rows (a multiple of 8, as S and T are) are one shorter
    tile, fetched when the sweep starts.
    Each sweep waits for its writes before the next one reads rho.
    Per step that is 16 * S * 128 bytes of HBM traffic, four m-vectors,
    against the resident form's one.

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

from repro.kernels.tiling import LANE as _LANE, column_rows
from repro.utils import compat

NBUF = 3  # column buffers in the fetch ring: NBUF - 1 fetches ahead
# scoped VMEM a form may plan for: the v5e's default limit is 16 MiB,
# and Mosaic keeps some of it for itself
VMEM_BUDGET = 12 * 2**20
_ROW_BYTES = 4 * _LANE      # one f32 row of 128 lanes
_TILED_BUFFERS = 12         # tiles the row-tiled form holds (see below)


def _tile_rows(S: int) -> int | None:
    """Rows of 128 lanes in a tile of the row-tiled form, or None where
    the resident form's NBUF + 2 whole (S, 128) tiles fit the budget.

    The row-tiled form holds two slots of (rho, column, column, rho
    out) tiles and one of each for the ragged tail: 12 tiles. A column
    it takes is over 2.4 tiles long, so a sweep has two whole tiles or
    more."""
    if (NBUF + 2) * S * _ROW_BYTES <= VMEM_BUDGET:
        return None
    return VMEM_BUDGET // (_TILED_BUFFERS * _ROW_BYTES) // 8 * 8


def _coordinate(sigma, step, dot, idx_ref, scsq_ref, dinv_ref, thr_ref,
                alpha_ref):
    """Step ``step``'s closed-form coordinate update from its dot
    rho^T c_j: stores the new alpha_j and returns sigma * (z - a), the
    axpy's scale. (The resident form inlines the same update, in the
    order its program has always had.)"""
    j = idx_ref[step]
    scsq = scsq_ref[step]                           # sigma*||c_j||^2
    a = alpha_ref[j]
    z_tilde = (scsq * a - dot) * dinv_ref[step]
    z = jnp.sign(z_tilde) * jnp.maximum(jnp.abs(z_tilde) - thr_ref[step], 0.0)
    z = jnp.where(scsq > 0, z, a)                   # padded/zero col
    alpha_ref[j] = z
    return sigma * (z - a)


def _copy_alpha(alpha_in_ref, alpha_ref):
    def copy(j, _):
        alpha_ref[j] = alpha_in_ref[j]
        return 0
    lax.fori_loop(0, alpha_ref.shape[0], copy, 0)


def _scd_kernel(sigma: float, idx_ref, scsq_ref, dinv_ref, thr_ref,
                worker_ref, A_hbm, alpha_in_ref, w_ref, alpha_ref, rho_ref,
                cols, sems):
    """The resident form: all H = idx.shape[0] sequential SCD updates
    with rho and the column ring in VMEM.

    The per-step scalars and the alpha coordinates live in SMEM (scalar
    reads and the dynamically indexed ``alpha[j]`` store run on the
    scalar unit); the fetched columns and the rho accumulator are
    (S, 128) VMEM tiles."""
    H = idx_ref.shape[0]
    k = worker_ref[0]

    def fetch(step):
        slot = step % NBUF
        return pltpu.make_async_copy(A_hbm.at[k, idx_ref[step]],
                                     cols.at[slot], sems.at[slot])

    for step in range(min(NBUF - 1, H)):
        fetch(step).start()

    _copy_alpha(alpha_in_ref, alpha_ref)
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


def _scd_tiled_kernel(sigma: float, T: int, idx_ref, scsq_ref, dinv_ref,
                      thr_ref, worker_ref, A_hbm, alpha_in_ref, w_hbm,
                      alpha_ref, rho_hbm, src, cols, out, sem_in, sem_out,
                      tail_src, tail_cols, tail_out, tail_sem_in,
                      tail_sem_out):
    """The row-tiled form: H + 1 sweeps over the rows in tiles of T,
    with rho in HBM (module docstring). ``src``/``cols``/``out`` are the
    two slots of full tiles, ``tail_*`` the ragged last tile."""
    H = idx_ref.shape[0]
    k = worker_ref[0]
    S = w_hbm.shape[0]
    n_full, tail = divmod(S, T)
    scalars = (idx_ref, scsq_ref, dinv_ref, thr_ref, alpha_ref)

    def sweep(source, prev, cur, delta):
        """One pass over the rows: rho = source + delta * c_prev (when
        ``prev`` is given, written back to HBM) and the dot of that rho
        with c_cur (when ``cur`` is given, returned)."""
        write = prev is not None
        # (HBM rows, full-tile buffer of a slot, tail buffer, semaphore)
        ops = [(source, lambda s: src.at[s], tail_src, 0)]
        if prev is not None:
            ops.append((A_hbm.at[k, prev], lambda s: cols.at[s, 0],
                        tail_cols.at[0], 1))
        if cur is not None:
            ops.append((A_hbm.at[k, cur], lambda s: cols.at[s, 1],
                        tail_cols.at[1], 2))

        def reads(t, slot):
            rows = pl.ds(pl.multiple_of(t * T, 8), T)
            return [pltpu.make_async_copy(hbm.at[rows], buf(slot),
                                          sem_in.at[slot, o])
                    for hbm, buf, _, o in ops]

        if tail:
            tail_rows = pl.ds(n_full * T, tail)
            tail_reads = [pltpu.make_async_copy(hbm.at[tail_rows], tbuf,
                                                tail_sem_in.at[o])
                          for hbm, _, tbuf, o in ops]
            if write:
                tail_write = pltpu.make_async_copy(
                    tail_out, rho_hbm.at[tail_rows], tail_sem_out.at[0])

        def write_back(t, slot):
            return pltpu.make_async_copy(
                out.at[slot], rho_hbm.at[pl.ds(pl.multiple_of(t * T, 8), T)],
                sem_out.at[slot])

        def tile(bufs, out_ref):
            rho = bufs[0][...]
            if write:
                rho = rho + delta * bufs[1][...].astype(jnp.float32)
                out_ref[...] = rho
            if cur is None:
                return jnp.float32(0.0)
            return jnp.sum(rho * bufs[-1][...].astype(jnp.float32))

        if tail:
            for c in tail_reads:
                c.start()
        for c in reads(0, 0):
            c.start()

        def body(t, acc):
            slot = t % 2

            @pl.when(t + 1 < n_full)
            def _():
                for c in reads(t + 1, 1 - slot):
                    c.start()

            for c in reads(t, slot):
                c.wait()
            if write:
                @pl.when(t >= 2)
                def _():
                    write_back(t - 2, slot).wait()
            acc = acc + tile([buf(slot) for _, buf, _, _ in ops],
                             out.at[slot])
            if write:
                write_back(t, slot).start()
            return acc

        acc = lax.fori_loop(0, n_full, body, jnp.float32(0.0))
        if tail:
            for c in tail_reads:
                c.wait()
            acc = acc + tile([tbuf for _, _, tbuf, _ in ops], tail_out)
            if write:
                tail_write.start()
        if write:
            # rho must be whole in HBM before the next sweep reads it
            for t in range(n_full - 2, n_full):
                write_back(t, t % 2).wait()
            if tail:
                tail_write.wait()
        return acc

    _copy_alpha(alpha_in_ref, alpha_ref)
    delta = _coordinate(sigma, 0, sweep(w_hbm, None, idx_ref[0], None),
                        *scalars)
    if H >= 2:
        delta = _coordinate(
            sigma, 1, sweep(w_hbm, idx_ref[0], idx_ref[1], delta), *scalars)

        def step(s, delta):
            dot = sweep(rho_hbm, idx_ref[s - 1], idx_ref[s], delta)
            return _coordinate(sigma, s, dot, *scalars)

        delta = lax.fori_loop(2, H, step, delta)
    sweep(rho_hbm if H >= 2 else w_hbm, idx_ref[H - 1], None, delta)


@functools.partial(jax.jit, static_argnames=("sigma", "lam_eta", "lam_l1",
                                             "interpret"))
def scd_pallas(A: jax.Array, csq: jax.Array, idx: jax.Array,
               alpha: jax.Array, w: jax.Array, worker: jax.Array | int = 0,
               *, sigma: float, lam_eta: float, lam_l1: float,
               interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Run H = idx.shape[0] SCD steps (any H >= 1) of worker
    ``worker``, fetching column ``A[worker, idx[s]]`` at step s, in the
    form S picks (module docstring).

    Args:
      A:     (K', n_local, S, 128) the workers' lane-tiled column
             blocks, S = ``column_rows(m)``, rows past m zero; or one
             worker's (n_local, S, 128) block, a stack of one.
      csq:   (H,) squared norms of the visited columns.
      idx:   (H,) int32 local coordinate index per step.
      alpha: (n_local,) f32 local coordinates.
      w:     (m,) round-start shared residual, f32.
      worker: the int32 index k of the worker's block in ``A``, traced
             or not; 0 for a lone block.
    Returns:
      (alpha_new (n_local,) f32, rho (m,) f32).
    """
    interpret = compat.default_interpret(interpret)
    if A.ndim == 3:
        A = A[None]                     # a stack of one: a bitcast
    _, n_local, S, lane = A.shape
    (H,), m = idx.shape, w.shape[0]
    assert H >= 1, H
    assert lane == _LANE and S == column_rows(m), (A.shape, m)
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

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    T = _tile_rows(S)
    if T is None:
        kernel = functools.partial(_scd_kernel, float(sigma))
        tile = pl.BlockSpec(memory_space=pltpu.VMEM)      # whole, one copy
        vector_spec = tile
        scratch = [pltpu.VMEM((NBUF, S, _LANE), A.dtype),
                   pltpu.SemaphoreType.DMA((NBUF,))]
    else:
        kernel = functools.partial(_scd_tiled_kernel, float(sigma), T)
        vector_spec = pl.BlockSpec(memory_space=pl.ANY)   # w, rho in HBM
        tail = S % T or 8          # the ragged last tile (unused if 0)
        scratch = [pltpu.VMEM((2, T, _LANE), jnp.float32),
                   pltpu.VMEM((2, 2, T, _LANE), A.dtype),
                   pltpu.VMEM((2, T, _LANE), jnp.float32),
                   pltpu.SemaphoreType.DMA((2, 3)),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.VMEM((tail, _LANE), jnp.float32),
                   pltpu.VMEM((2, tail, _LANE), A.dtype),
                   pltpu.VMEM((tail, _LANE), jnp.float32),
                   pltpu.SemaphoreType.DMA((3,)),
                   pltpu.SemaphoreType.DMA((1,))]
    alpha_out, rho = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # idx, sigma*csq, 1/denom, threshold: whole (H,) in SMEM;
            # the worker's index, (1,)
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),        # stack, in HBM
                smem,                                     # alpha in
                vector_spec,                              # w
            ],
            out_specs=[smem, vector_spec],                # alpha, rho
            scratch_shapes=scratch),
        out_shape=[
            jax.ShapeDtypeStruct((n_local,), jnp.float32),
            jax.ShapeDtypeStruct((S, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="scd",
    )(idx.astype(jnp.int32), scsq, dinv, thr,
      jnp.asarray(worker, jnp.int32).reshape(1), A,
      alpha.astype(jnp.float32), w3)
    return alpha_out, rho.reshape(mp)[:m]
