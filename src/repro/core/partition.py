"""Column partitioning of the data matrix across workers.

Two strategies, mirroring the paper:
  * ``block``     — contiguous equal-width column blocks (what Spark's
                    default partitioning gives after a columnar load).
  * ``balanced``  — the paper's MPI load-balancing partitioner: greedy
                    bin-packing so that sum_i nnz(c_i) is roughly equal
                    per partition.

Both return a permutation + per-worker index sets. ``pack_columns``
lays the worker blocks out on the host as the (K, n_pad, S, 128) column
tiles that the virtual-worker and shard_map drivers and the SCD solvers
read: columns and rows zero-padded, S = ``tiling.column_rows(m)`` rows
of 128 lanes, whole (8, 128) tiles. The stack is uploaded as it is: its
minor dimensions, a column's rows of lanes, need no padding on the
device, where a (K, m, n_pad) stack's minor n_pad would be padded to
128 lanes (18x the stack at n_pad = 7). ``column_norms`` then reads the
uploaded stack once for the columns' squared norms.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.tiling import LANE, column_rows


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
    """Lay worker column-blocks out as (K, n_pad, S, 128) with zero
    padding: columns to the common width n_pad, rows to
    mp = S * 128, S = ``column_rows(m)``. Column j of worker k is the
    contiguous, tile-aligned slab ``[k, j]``: the (S, 128) tile the SCD
    kernel fetches and computes on.

    Returns (A_tiles, mask) where mask is (K, n_pad) with 1.0 for real
    columns. Zero-padded columns have zero norm; the SCD solvers guard
    against picking them (update is exactly 0 for an all-zero column, and
    the sampling distribution masks them out). Zero rows change no dot
    product or norm, and the solvers drop them from rho.
    """
    m, _ = A.shape
    K, n_pad = part.K, part.n_padded
    S = column_rows(m)
    out = np.zeros((K, n_pad, S * LANE), dtype=A.dtype)
    mask = np.zeros((K, n_pad), dtype=A.dtype)
    for k, ids in enumerate(part.owned):
        out[k, : len(ids), :m] = A[:, ids].T
        mask[k, : len(ids)] = 1.0
    return out.reshape(K, n_pad, S, LANE), mask


@jax.jit
def column_norms(A_tiles: jax.Array) -> jax.Array:
    """The (K, n_pad) squared norms of the packed columns' tiles."""
    return jnp.sum(A_tiles * A_tiles, axis=(2, 3))


def unpack_alpha(alpha_stacked: np.ndarray, part: Partition, n: int) -> np.ndarray:
    """Scatter stacked per-worker alpha blocks back to global coordinates."""
    alpha = np.zeros(n, dtype=alpha_stacked.dtype)
    for k, ids in enumerate(part.owned):
        alpha[ids] = alpha_stacked[k, : len(ids)]
    return alpha
