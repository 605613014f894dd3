"""Column partitioning of the data matrix across workers.

Two strategies, mirroring the paper:
  * ``block``     — contiguous equal-width column blocks (what Spark's
                    default partitioning gives after a columnar load).
  * ``balanced``  — the paper's MPI load-balancing partitioner: greedy
                    bin-packing so that sum_i nnz(c_i) is roughly equal
                    per partition.

Both return a permutation + per-worker index sets. ``pack_columns``
stacks the worker blocks on the host as (K, mp, n_pad) (columns and
rows zero-padded), and ``tile_columns`` lays that stack out once on the
device as the (K, n_pad, S, 128) column tiles that the virtual-worker
and shard_map drivers and the SCD solvers read.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tiling import LANE


@dataclass(frozen=True)
class Partition:
    K: int
    # index sets: list of np arrays of column ids, one per worker
    owned: tuple
    n_padded: int  # common padded width

    @property
    def sizes(self) -> np.ndarray:
        return np.array([len(p) for p in self.owned])


def block_partition(n: int, K: int) -> Partition:
    ids = np.arange(n)
    chunks = np.array_split(ids, K)
    n_pad = max(len(c) for c in chunks)
    return Partition(K=K, owned=tuple(chunks), n_padded=n_pad)


def balanced_partition(nnz_per_col: np.ndarray, K: int) -> Partition:
    """Greedy largest-first bin packing on per-column nonzero counts."""
    n = len(nnz_per_col)
    order = np.argsort(-nnz_per_col, kind="stable")
    loads = np.zeros(K)
    buckets: list[list[int]] = [[] for _ in range(K)]
    for j in order:
        k = int(np.argmin(loads))
        buckets[k].append(int(j))
        loads[k] += nnz_per_col[j]
    owned = tuple(np.array(sorted(bkt), dtype=np.int64) for bkt in buckets)
    n_pad = max(len(b) for b in buckets)
    return Partition(K=K, owned=owned, n_padded=n_pad)


def partition_imbalance(part: Partition, nnz_per_col: np.ndarray) -> float:
    """max/mean per-worker nnz load — 1.0 is perfectly balanced."""
    loads = np.array([nnz_per_col[p].sum() for p in part.owned], dtype=np.float64)
    return float(loads.max() / max(loads.mean(), 1e-12))


def pack_columns(A: np.ndarray, part: Partition) -> tuple[np.ndarray, np.ndarray]:
    """Stack worker column-blocks into (K, mp, n_pad) with zero padding:
    columns to the common width n_pad, rows to mp = ceil(m/128)*128, the
    lane multiple ``tile_columns`` lays out.

    Returns (A_stacked, mask) where mask is (K, n_pad) with 1.0 for real
    columns. Zero-padded columns have zero norm; the SCD solvers guard
    against picking them (update is exactly 0 for an all-zero column, and
    the sampling distribution masks them out). Zero rows change no dot
    product or norm, and the solvers drop them from rho.
    """
    m, _ = A.shape
    K, n_pad = part.K, part.n_padded
    out = np.zeros((K, -(-m // LANE) * LANE, n_pad), dtype=A.dtype)
    mask = np.zeros((K, n_pad), dtype=A.dtype)
    for k, ids in enumerate(part.owned):
        out[k, :m, : len(ids)] = A[:, ids]
        mask[k, : len(ids)] = 1.0
    return out, mask


@functools.partial(jax.jit, donate_argnums=0)
def tile_columns(A_st: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The packed (K, mp, n_pad) stack on the device -> its
    (K, n_pad, S, 128) column tiles, S = mp / 128, and their (K, n_pad)
    squared norms.

    Column j of worker k becomes the contiguous, tile-aligned slab
    ``[k, j]``: the (S, 128) tile the SCD kernel fetches and computes on.
    The packed stack is donated, so the tiles can take its buffer: the
    layout costs one stack of device memory more, not two.
    """
    K, mp, n_pad = A_st.shape
    assert mp % LANE == 0, mp
    tiles = A_st.reshape(K, mp // LANE, LANE, n_pad).transpose(0, 3, 1, 2)
    return tiles, jnp.sum(tiles * tiles, axis=(2, 3))


def unpack_alpha(alpha_stacked: np.ndarray, part: Partition, n: int) -> np.ndarray:
    """Scatter stacked per-worker alpha blocks back to global coordinates."""
    alpha = np.zeros(n, dtype=alpha_stacked.dtype)
    for k, ids in enumerate(part.owned):
        alpha[ids] = alpha_stacked[k, : len(ids)]
    return alpha
