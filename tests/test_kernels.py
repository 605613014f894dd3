"""Pallas SCD kernel vs the pure-jnp oracle on the lane-tiled
(n_local, S, 128) column block: shape/dtype sweeps, a float64 numpy SCD
on the plain (m, n_local) matrix, which shows the block's zero rows
exact, and hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev extra; CI installs it via .[dev]
from hypothesis import given, settings, strategies as st

from repro.core.partition import block_partition, pack_columns, tile_columns
from repro.kernels import scd_steps_kernel, scd_steps_ref


def tiles(A):
    """(m, n) -> the (n, ceil(m/128), 128) block the solvers read."""
    A = np.asarray(A, np.float32)
    packed, _ = pack_columns(A, block_partition(A.shape[1], 1))
    return tile_columns(jnp.asarray(packed))[0][0]


def _mk(m, n, H, dtype, seed=0):
    """(tiled block, colsq, alpha, w, idx) of a random (m, n) problem."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((m, n)), dtype).astype(jnp.float32)
    colsq = jnp.sum(A ** 2, axis=0)
    alpha = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal(m), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, H), jnp.int32)
    return tiles(A), colsq, alpha, w, idx


def scd_numpy(A, colsq, alpha, w, idx, *, sigma, lam, eta):
    """Sequential SCD in float64 on the plain (m, n) matrix."""
    A, alpha, rho = (np.asarray(x, np.float64) for x in (A, alpha, w))
    alpha, w0 = alpha.copy(), rho.copy()
    for j in np.asarray(idx):
        c, csq, a = A[:, j], float(colsq[j]), alpha[j]
        if csq == 0:
            continue
        denom = sigma * csq + lam * eta
        z = (sigma * csq * a - rho @ c) / denom
        z = np.sign(z) * max(abs(z) - lam * (1 - eta) / denom, 0.0)
        alpha[j] = z
        rho = rho + sigma * (z - a) * c
    return (rho - w0) / sigma, alpha


@pytest.mark.parametrize("m,n,H", [
    (32, 16, 8), (64, 64, 64), (128, 96, 200),
    (256, 17, 7), (512, 128, 333), (33, 5, 1),
])
def test_kernel_matches_oracle_shapes(m, n, H):
    A, colsq, alpha, w, idx = _mk(m, n, H, jnp.float32, seed=m + n + H)
    kw = dict(sigma=8.0, lam=1.0, eta=1.0)
    dv_r, a_r = scd_steps_ref(A, colsq, alpha, w, idx, **kw)
    dv_k, a_k = scd_steps_kernel(A, colsq, alpha, w, idx, **kw)
    np.testing.assert_allclose(dv_r, dv_k, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(a_r, a_k, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("eta", [0.0, 0.3, 1.0])
def test_kernel_matches_oracle_elastic_net(eta):
    A, colsq, alpha, w, idx = _mk(96, 48, 120, jnp.float32, seed=11)
    kw = dict(sigma=4.0, lam=2.5, eta=eta)
    dv_r, a_r = scd_steps_ref(A, colsq, alpha, w, idx, **kw)
    dv_k, a_k = scd_steps_kernel(A, colsq, alpha, w, idx, **kw)
    np.testing.assert_allclose(dv_r, dv_k, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(a_r, a_k, rtol=1e-4, atol=1e-5)


def test_kernel_bf16_stream_close_to_f32_oracle():
    """bf16-quantized column data with f32 accumulation stays near the
    oracle."""
    rng = np.random.default_rng(5)
    m, n, H = 128, 64, 96
    A32 = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    Abf = A32.astype(jnp.bfloat16).astype(jnp.float32)  # quantized data
    colsq = jnp.sum(Abf ** 2, axis=0)
    alpha = jnp.zeros(n, jnp.float32)
    w = jnp.asarray(rng.standard_normal(m), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, H), jnp.int32)
    kw = dict(sigma=8.0, lam=1.0, eta=1.0)
    dv_r, a_r = scd_steps_ref(tiles(Abf), colsq, alpha, w, idx, **kw)
    dv_k, a_k = scd_steps_kernel(tiles(Abf), colsq, alpha, w, idx, **kw)
    np.testing.assert_allclose(dv_r, dv_k, rtol=1e-4, atol=1e-4)


def test_kernel_duplicate_indices_sequential_semantics():
    """Visiting the same coordinate twice must apply updates sequentially."""
    A, colsq, alpha, w, _ = _mk(64, 8, 0, jnp.float32, seed=2)
    idx = jnp.asarray([3, 3, 3, 5, 3, 5], jnp.int32)
    kw = dict(sigma=2.0, lam=0.5, eta=0.8)
    dv_r, a_r = scd_steps_ref(A, colsq, alpha, w, idx, **kw)
    dv_k, a_k = scd_steps_kernel(A, colsq, alpha, w, idx, **kw)
    np.testing.assert_allclose(dv_r, dv_k, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a_r, a_k, rtol=1e-5, atol=1e-6)


def test_kernel_zero_column_noop():
    """Padded (all-zero) columns must leave state untouched."""
    A, colsq, alpha, w, _ = _mk(32, 6, 0, jnp.float32, seed=3)
    A = A.at[2].set(0.0)
    colsq = colsq.at[2].set(0.0)
    idx = jnp.asarray([2, 2, 2], jnp.int32)
    dv, a_new = scd_steps_kernel(A, colsq, alpha, w, idx,
                                 sigma=2.0, lam=1.0, eta=1.0)
    np.testing.assert_allclose(dv, np.zeros(32), atol=1e-7)
    np.testing.assert_allclose(a_new, alpha, atol=1e-7)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(8, 96),
    n=st.integers(2, 48),
    H=st.integers(1, 150),
    sigma=st.floats(1.0, 16.0),
    lam=st.floats(0.1, 4.0),
    eta=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**16),
)
def test_kernel_oracle_property(m, n, H, sigma, lam, eta, seed):
    A, colsq, alpha, w, idx = _mk(m, n, H, jnp.float32, seed=seed)
    kw = dict(sigma=sigma, lam=lam, eta=eta)
    dv_r, a_r = scd_steps_ref(A, colsq, alpha, w, idx, **kw)
    dv_k, a_k = scd_steps_kernel(A, colsq, alpha, w, idx, **kw)
    np.testing.assert_allclose(dv_r, dv_k, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(a_r, a_k, rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), H=st.integers(1, 64))
def test_scd_decreases_subproblem_objective(seed, H):
    """Each SCD epoch must not increase the local subproblem objective
    G_k(dalpha) = w.A da + sigma/2 ||A da||^2 + reg(alpha+da) - reg(alpha)."""
    rng = np.random.default_rng(seed)
    m, n, sigma, lam, eta = 48, 24, 4.0, 1.0, 0.7
    A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    colsq = jnp.sum(A * A, 0)
    alpha0 = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal(m), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, H), jnp.int32)
    dv, alpha1 = scd_steps_ref(tiles(A), colsq, alpha0, w, idx,
                               sigma=sigma, lam=lam, eta=eta)

    def G(alpha):
        da = alpha - alpha0
        Ada = A @ da
        reg = lam * (eta / 2 * jnp.sum(alpha ** 2)
                     + (1 - eta) * jnp.sum(jnp.abs(alpha)))
        return float(w @ Ada + sigma / 2 * Ada @ Ada + reg)

    assert G(np.asarray(alpha1)) <= G(np.asarray(alpha0)) + 1e-4


@pytest.mark.parametrize("m,n,H,case", [
    (200, 12, 40, "m not a multiple of 128"),
    (384, 10, 2, "H shorter than the fetch ring"),
    (130, 6, 30, "repeated indices"),
    (257, 9, 45, "padded zero columns"),
])
def test_kernel_matches_oracle_on_tiled_block(m, n, H, case):
    """The kernel, the jnp oracle and a float64 SCD on the plain
    (m, n) matrix agree on the tiled block: the zero rows past m change
    nothing, a sweep shorter than the fetch ring never fetches past its
    end, a revisited column sees its own earlier update, and a zero
    column is a no-op."""
    rng = np.random.default_rng(m + n + H)
    A = rng.standard_normal((m, n)).astype(np.float32)
    if case == "padded zero columns":
        A[:, -3:] = 0.0                      # as pack_columns pads
    idx = rng.integers(0, n, H).astype(np.int32)
    if case == "repeated indices":
        idx[::3] = idx[0]
    colsq = jnp.asarray((A.astype(np.float64) ** 2).sum(0), jnp.float32)
    alpha = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal(m), jnp.float32)
    kw = dict(sigma=4.0, lam=1.5, eta=0.6)
    dv_r, a_r = scd_steps_ref(tiles(A), colsq, alpha, w, jnp.asarray(idx),
                              **kw)
    dv_k, a_k = scd_steps_kernel(tiles(A), colsq, alpha, w,
                                 jnp.asarray(idx), **kw)
    dv_n, a_n = scd_numpy(A, colsq, alpha, w, idx, **kw)
    assert dv_k.shape == (m,) and a_k.shape == (n,)
    np.testing.assert_allclose(dv_k, dv_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a_k, a_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dv_k, dv_n, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(a_k, a_n, rtol=1e-4, atol=1e-4)
    if case == "padded zero columns":
        np.testing.assert_array_equal(a_k[-3:], alpha[-3:])


def test_tile_columns_layout():
    """Column j of worker k is slab [k, j], its rows past m zero, and
    its squared norm is col_sq[k, j]."""
    rng = np.random.default_rng(0)
    A = rng.standard_normal((200, 15)).astype(np.float32)
    part = block_partition(15, 3)
    packed, _ = pack_columns(A, part)
    assert packed.shape == (3, 256, 5)
    T, col_sq = tile_columns(jnp.asarray(packed))
    assert T.shape == (3, 5, 2, 128) and col_sq.shape == (3, 5)
    flat = np.asarray(T).reshape(3, 5, 256)
    for k, ids in enumerate(part.owned):
        np.testing.assert_array_equal(flat[k, :, :200], A[:, ids].T)
        np.testing.assert_allclose(col_sq[k], (A[:, ids] ** 2).sum(0),
                                   rtol=1e-6)
    np.testing.assert_array_equal(flat[..., 200:], 0.0)
