"""Jitted public wrapper around the Pallas SCD kernel.

``scd_steps_kernel`` matches the contract of the pure-jnp oracle
``repro.kernels.ref.scd_steps_ref`` exactly, so the two are drop-in
interchangeable as CoCoA local solvers (``CoCoAConfig.solver``). The
wrapper's only job is the one XLA gather that turns the random-access
column visits into the dense (H, m) stream the kernel pipelines;
padding, lane tiling and block sizing all live in ``scd_pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.scd import scd_pallas


@functools.partial(jax.jit,
                   static_argnames=("sigma", "lam", "eta", "h_blk", "interpret"))
def scd_steps_kernel(A_k: jax.Array, col_sq: jax.Array, alpha_k: jax.Array,
                     w: jax.Array, idx: jax.Array, *, sigma: float,
                     lam: float, eta: float, h_blk: int | None = None,
                     interpret: bool | None = None):
    """H SCD steps on one worker's column block via the Pallas kernel.

    Same signature/returns as ``repro.core.solvers.scd_steps``:
      A_k (m, n_local), col_sq (n_local,), alpha_k (n_local,), w (m,),
      idx (H,) int32  ->  (delta_v (m,), alpha_new (n_local,)).
    ``h_blk=None`` lets the kernel size its grid block from the VMEM
    budget.
    """
    with jax.named_scope("gather"):
        cols = jnp.take(A_k, idx, axis=1).T          # (H, m) pre-gather
        col_sq_h = col_sq[idx]
    alpha_new, rho = scd_pallas(
        cols, col_sq_h, idx, alpha_k.astype(jnp.float32), w,
        sigma=float(sigma), lam_eta=float(lam * eta),
        lam_l1=float(lam * (1.0 - eta)), h_blk=h_blk,
        interpret=interpret)
    delta_v = (rho - w) / jnp.asarray(sigma, rho.dtype)
    return delta_v.astype(w.dtype), alpha_new.astype(alpha_k.dtype)
