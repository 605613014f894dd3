"""Jitted public wrapper around the Pallas SCD kernel.

``scd_steps_kernel`` matches the contract of the pure-jnp oracle
``repro.kernels.ref.scd_steps_ref`` exactly, so the two are drop-in
interchangeable as CoCoA local solvers (``CoCoAConfig.solver``). Both
take the worker's block in the lane-tiled ``(n_local, S, 128)`` layout
of ``partition.pack_columns``. The kernel fetches each visited column
from that block itself (an index-driven DMA, see ``repro.kernels.scd``),
so the wrapper only picks out the visited columns' squared norms and
turns the kernel's residual into the update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.scd import scd_pallas


@functools.partial(jax.jit,
                   static_argnames=("sigma", "lam", "eta", "interpret"))
def scd_steps_kernel(A_k: jax.Array, col_sq: jax.Array, alpha_k: jax.Array,
                     w: jax.Array, idx: jax.Array, *, sigma: float,
                     lam: float, eta: float, interpret: bool | None = None):
    """H SCD steps on one worker's column block via the Pallas kernel.

    Same signature/returns as ``repro.core.solvers.scd_steps``:
      A_k (n_local, S, 128), col_sq (n_local,), alpha_k (n_local,),
      w (m,), idx (H,) int32  ->  (delta_v (m,), alpha_new (n_local,)).
    """
    alpha_new, rho = scd_pallas(
        A_k, col_sq[idx], idx, alpha_k.astype(jnp.float32), w,
        sigma=float(sigma), lam_eta=float(lam * eta),
        lam_l1=float(lam * (1.0 - eta)), interpret=interpret)
    delta_v = (rho - w) / jnp.asarray(sigma, rho.dtype)
    return delta_v.astype(w.dtype), alpha_new.astype(alpha_k.dtype)
