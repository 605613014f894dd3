"""The plain ridge reference, independent of the program under test.

Ridge on the column-partitioned problem, as the program states it:

    P(alpha) = 1/2 ||A alpha - b||^2 + lam/2 ||alpha||^2

* ``p_star``: the optimum from the n x n normal equations. The Gram
  matrix and A^T b are formed on the device at ``HIGHEST`` precision,
  the system is solved on the host in float64, and P is evaluated at
  that alpha by ``primal64``.
* ``primal64``: P(alpha) on the host in float64, in blocks of rows.
* ``cocoa_solve``: CoCoA with H steps of stochastic coordinate descent
  per worker and round, immediate local updates, sigma = K, in plain
  ``jax.numpy`` at a stated dtype. At float32 it is a second witness
  beside the program; at bfloat16 it is the control that the
  comparison has to refuse.

Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def primal64(A: np.ndarray, b: np.ndarray, alpha: np.ndarray, lam: float,
             block: int = 16384) -> float:
    alpha = np.asarray(alpha, np.float64)
    b = np.asarray(b, np.float64)
    loss = 0.0
    for i in range(0, A.shape[0], block):
        r = A[i:i + block].astype(np.float64) @ alpha - b[i:i + block]
        loss += float(r @ r)
    return 0.5 * loss + 0.5 * lam * float(alpha @ alpha)


@jax.jit
def _normal_equations(A, b):
    return (jnp.dot(A.T, A, precision=HI), jnp.dot(A.T, b, precision=HI))


def p_star(A_dev, A: np.ndarray, b: np.ndarray, lam: float):
    """``(p*, alpha*)``: the ridge optimum of (A, b); ``A_dev`` is the
    same matrix on the device, used for the Gram matrix only."""
    G, c = _normal_equations(A_dev, jnp.asarray(b))
    G = np.asarray(G, np.float64)
    alpha = np.linalg.solve(G + lam * np.eye(G.shape[0]),
                            np.asarray(c, np.float64))
    return primal64(A, b, alpha, lam), alpha


def column_blocks(A_dev, K: int, dtype):
    """The K contiguous column blocks of A as rows: (K, n / K, m)."""
    m, n = A_dev.shape
    if n % K:
        raise ValueError(f"{n} columns do not split into {K} equal blocks")
    return A_dev.T.reshape(K, n // K, m).astype(dtype)


@functools.partial(jax.jit, static_argnames=("H", "lam"))
def cocoa_round(blocks, csq, alpha, w, key, *, H: int, lam: float):
    """One CoCoA round at the dtype of ``blocks``/``alpha``/``w``:
    each worker runs H coordinate steps on its block against a local
    copy of the residual w = A alpha - b, then w absorbs every worker's
    update. Returns the new state and the primal at it."""
    K, n_local, _ = blocks.shape
    dt = w.dtype
    sigma = jnp.asarray(K, dt)
    prec = HI if dt == jnp.float32 else None

    def worker(A_k, csq_k, a, k):
        idx = jax.random.randint(k, (H,), 0, n_local)

        def step(i, carry):
            a, rho = carry
            j = idx[i]
            c = A_k[j]
            aj = a[j]
            z = ((sigma * csq_k[j] * aj - jnp.dot(rho, c, precision=prec))
                 / (sigma * csq_k[j] + jnp.asarray(lam, dt))).astype(dt)
            return a.at[j].set(z), (rho + (sigma * (z - aj)) * c).astype(dt)

        a, rho = jax.lax.fori_loop(0, H, step, (a, w))
        return a, ((rho - w) / sigma).astype(dt)

    alpha, dv = jax.vmap(worker)(blocks, csq, alpha,
                                 jax.random.split(key, K))
    w = (w + jnp.sum(dv, axis=0)).astype(dt)
    primal = 0.5 * jnp.sum(w * w) + 0.5 * lam * jnp.sum(alpha * alpha)
    return alpha, w, primal


def cocoa_solve(A_dev, b, *, K: int, H: int, lam: float, eps: float,
                p_star: float, p_zero: float, max_rounds: int, seed: int,
                dtype=jnp.float32):
    """Rounds from alpha = 0 until the solve's own primal certifies
    ``eps`` (or ``max_rounds``). Returns the global alpha (float64 on
    the host), the last primal it reported, its rounds, and whether it
    certified."""
    blocks = column_blocks(A_dev, K, dtype)
    csq = jnp.sum(blocks.astype(jnp.float32) ** 2, axis=2).astype(dtype)
    alpha = jnp.zeros(blocks.shape[:2], dtype)
    w = (-jnp.asarray(b)).astype(dtype)
    key = jax.random.key(seed)
    primal, rounds, reached = float("nan"), 0, False
    while rounds < max_rounds and not reached:
        key, sub = jax.random.split(key)
        alpha, w, p = cocoa_round(blocks, csq, alpha, w, sub, H=H, lam=lam)
        rounds += 1
        primal = float(p)
        reached = (primal - p_star) / (p_zero - p_star) <= eps
    return (np.asarray(alpha, np.float64).reshape(-1), primal, rounds,
            reached)
