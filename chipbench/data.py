"""An epsilon-shaped ridge problem, made on the device from a seed.

epsilon (LIBSVM binary collection, from the PASCAL Large Scale Learning
Challenge 2008) is 400,000 x 2,000, dense, with +-1 labels; its features
are standardised and each row is scaled to unit L2 norm. No file is
read: a configuration gives the shape and the assumed generator, and
``make_problem`` draws the matrix in one jitted call:

    x_i = sqrt(1 - s) z_i + sqrt(s) f_i U        (z, f, U standard normal)
    y_i = sign(x_i . beta / sqrt(n) + noise * e_i)

with ``r`` shared factors carrying a share ``s`` of each raw feature's
variance, then the columns standardised and the rows scaled to unit
norm. Rows are drawn in blocks (``lax.map``) so that the transient
device memory is one block, not a second copy of the matrix.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int) -> list[int]:
    """``n`` independent 32-bit words from a seed of any size: the data
    key, the trainer's coordinate seed, the sample of solves checked."""
    return [int(x) for x in np.random.SeedSequence(int(seed)).generate_state(n)]


@functools.partial(jax.jit, static_argnames=(
    "rows", "features", "factors", "factor_share", "label_noise",
    "block_rows"))
def _make(key, *, rows, features, factors, factor_share, label_noise,
          block_rows):
    k_z, k_f, k_u, k_beta, k_e = jax.random.split(key, 5)
    hi = jax.lax.Precision.HIGHEST
    U = jax.random.normal(k_u, (factors, features)) / math.sqrt(factors)
    beta = jax.random.normal(k_beta, (features,))

    def block(i):
        z = jax.random.normal(jax.random.fold_in(k_z, i),
                              (block_rows, features))
        f = jax.random.normal(jax.random.fold_in(k_f, i),
                              (block_rows, factors))
        x = (math.sqrt(1.0 - factor_share) * z
             + math.sqrt(factor_share) * jnp.dot(f, U, precision=hi))
        e = jax.random.normal(jax.random.fold_in(k_e, i), (block_rows,))
        score = (jnp.dot(x, beta, precision=hi) / math.sqrt(features)
                 + label_noise * e)
        return x, jnp.where(score >= 0, 1.0, -1.0)

    X, y = jax.lax.map(block, jnp.arange(rows // block_rows))
    X = X.reshape(rows, features)
    X = (X - X.mean(axis=0)) / X.std(axis=0)
    X = X / jnp.linalg.norm(X, axis=1, keepdims=True)
    return X.astype(jnp.float32), y.reshape(rows).astype(jnp.float32)


def make_problem(cfg: dict, seed: int):
    """``(A, b)`` on the default device: A (rows, features) float32, b
    the +-1 labels, from the configuration's ``data`` group and a seed."""
    d = cfg["data"]
    rows = cfg["rows"]
    block = min(rows, d["block_rows"])
    if rows % block:
        raise ValueError(f"rows {rows} is not a multiple of block_rows "
                         f"{block}")
    key = jax.random.key(seed_words(seed, 1)[0])
    return _make(key, rows=rows, features=cfg["features"],
                 factors=d["factors"], factor_share=d["factor_share"],
                 label_noise=d["label_noise"], block_rows=block)
