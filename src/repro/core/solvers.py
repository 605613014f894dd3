"""Local sub-problem solvers (pure-jnp reference implementations).

The CoCoA local subproblem on worker k (elastic net, Appendix A):

    min_{dalpha}  w^T A dalpha + sigma/2 ||A dalpha||^2
                  + sum_{i in P_k} lam*(eta/2 (alpha+dalpha)_i^2
                                        + (1-eta)|(alpha+dalpha)_i|)

solved by H steps of stochastic coordinate descent with *immediate local
updates* (this is what distinguishes CoCoA from mini-batch SCD). The
closed-form single-coordinate update, with local residual state
``rho = w + sigma * A dalpha``:

    z_tilde = (sigma*||c_j||^2 * a_j - rho^T c_j) / (sigma*||c_j||^2 + lam*eta)
    z       = soft_threshold(z_tilde, lam*(1-eta)/(sigma*||c_j||^2 + lam*eta))
    rho    += sigma * c_j * (z - a_j)

The Pallas TPU kernel in ``repro.kernels.scd`` implements the identical
contract (this module is its ``ref`` oracle's home).

Coordinate indices are pre-sampled by the caller so that the reference
and the kernel are bit-comparable given the same index stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def soft_threshold(z: jax.Array, tau) -> jax.Array:
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - tau, 0.0)


def _column(A_k: jax.Array, j, m: int) -> jax.Array:
    """Column j, as an m-vector, of a lane-tiled (n_local, S, 128) block."""
    return lax.dynamic_index_in_dim(A_k, j, axis=0,
                                    keepdims=False).reshape(-1)[:m]


@functools.partial(jax.jit, static_argnames=("unroll",))
def scd_steps(A_k: jax.Array, col_sq: jax.Array, alpha_k: jax.Array,
              w: jax.Array, idx: jax.Array, *, sigma: float, lam: float,
              eta: float, unroll: int = 1):
    """Run len(idx) sequential SCD steps on one worker's column block.

    Args:
      A_k:    (n_local, S, 128) the local column block, lane-tiled by
              ``partition.pack_columns``: column j is ``A_k[j]`` with its
              m rows zero-padded to S*128 (zero-padded cols ok).
      col_sq: (n_local,) squared column norms of A_k.
      alpha_k:(n_local,) local coordinates of alpha.
      w:      (m,) shared residual vector  w = A alpha - b  at round start.
      idx:    (H,) int32 coordinate indices to visit (sampled by caller).

    Returns:
      (delta_v, alpha_new): the m-vector update  A_k @ dalpha  to be
      all-reduced, and the updated local alpha block.
    """
    sigma = jnp.asarray(sigma, w.dtype)
    lam_eta = jnp.asarray(lam * eta, w.dtype)
    lam_l1 = jnp.asarray(lam * (1.0 - eta), w.dtype)

    def body(i, carry):
        alpha, rho = carry
        j = idx[i]
        c = _column(A_k, j, w.shape[0])
        csq = col_sq[j]
        a = alpha[j]
        denom = sigma * csq + lam_eta
        # Zero (padded) column -> denom reduces to lam_eta; numerator keeps
        # z == shrinkage of a; guard to make it an exact no-op instead.
        z_tilde = (sigma * csq * a - jnp.dot(rho, c)) / denom
        z = soft_threshold(z_tilde, lam_l1 / denom)
        z = jnp.where(csq > 0, z, a)
        alpha = alpha.at[j].set(z)
        rho = rho + (sigma * (z - a)) * c
        return alpha, rho

    alpha_new, rho = lax.fori_loop(0, idx.shape[0], body, (alpha_k, w),
                                   unroll=unroll)
    delta_v = (rho - w) / sigma
    return delta_v, alpha_new


@functools.partial(jax.jit, static_argnames=())
def scd_steps_fixed_point(A_k, col_sq, alpha_k, w, idx, *, sigma, lam, eta):
    """Mini-batch SCD (SDCA-style) — same coordinate rule but WITHOUT
    immediate local updates: every step sees the round-start residual.
    This is the paper's mini-batch baseline; aggregation across the batch
    is damped by 1/sigma at the caller."""
    sigma = jnp.asarray(sigma, w.dtype)
    lam_eta = jnp.asarray(lam * eta, w.dtype)
    lam_l1 = jnp.asarray(lam * (1.0 - eta), w.dtype)

    def body(i, carry):
        alpha, dv = carry
        j = idx[i]
        c = _column(A_k, j, w.shape[0])
        csq = col_sq[j]
        a = alpha[j]
        denom = sigma * csq + lam_eta
        z_tilde = (sigma * csq * a - jnp.dot(w, c)) / denom   # fixed residual w
        z = soft_threshold(z_tilde, lam_l1 / denom)
        z = jnp.where(csq > 0, z, a)
        alpha = alpha.at[j].set(z)
        dv = dv + (z - a) * c
        return alpha, dv

    alpha_new, dv = lax.fori_loop(0, idx.shape[0], body,
                                  (alpha_k, jnp.zeros_like(w)))
    return dv, alpha_new
