"""Pallas SCD kernel vs the pure-jnp oracle on the lane-tiled
(n_local, S, 128) column block: shape/dtype sweeps, a float64 numpy SCD
on the plain (m, n_local) matrix, which shows the block's zero rows
exact, and hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev extra; CI installs it via .[dev]
from hypothesis import given, settings, strategies as st

from repro.core.partition import block_partition, column_norms, pack_columns
from repro.kernels import scd_steps_kernel, scd_steps_ref
from repro.kernels.tiling import column_rows


def tiles(A):
    """(m, n) -> the (n, column_rows(m), 128) block the solvers read."""
    A = np.asarray(A, np.float32)
    packed, _ = pack_columns(A, block_partition(A.shape[1], 1))
    return jnp.asarray(packed[0])


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
    T, _ = pack_columns(A, part)
    assert T.shape == (3, 5, 8, 128)          # one whole (8, 128) tile
    col_sq = column_norms(jnp.asarray(T))
    assert col_sq.shape == (3, 5)
    flat = T.reshape(3, 5, 1024)
    for k, ids in enumerate(part.owned):
        np.testing.assert_array_equal(flat[k, :, :200], A[:, ids].T)
        np.testing.assert_allclose(col_sq[k], (A[:, ids] ** 2).sum(0),
                                   rtol=1e-6)
    np.testing.assert_array_equal(flat[..., 200:], 0.0)


# the row-tiled form, reached as the trainer reaches it: scd_pallas
# picks it from S against the kernel's VMEM budget, shrunk here so that
# small columns take several row tiles
_TILE = 16                                  # rows of a tile at this budget


@pytest.fixture
def small_vmem(monkeypatch):
    """A VMEM budget of 12 tiles of 16 rows: columns of S >= 40 lane rows
    (m > 4,096) take the row-tiled form."""
    import jax
    from repro.kernels import scd as scd_mod

    monkeypatch.setattr(scd_mod, "VMEM_BUDGET",
                        scd_mod._TILED_BUFFERS * _TILE * 4 * 128)
    jax.clear_caches()          # no program of another budget is reused
    yield scd_mod
    jax.clear_caches()


@pytest.mark.parametrize("S,n,H,case", [
    (40, 9, 13, "shorter last tile, rows past m"),
    (48, 9, 13, "rows a whole number of tiles"),
    (56, 6, 24, "repeated indices"),
    (40, 9, 11, "padded zero columns"),
    (40, 5, 1, "H shorter than the fetch ring"),
    (48, 5, 2, "H shorter than the fetch ring"),
])
def test_row_tiled_kernel_matches_oracle(small_vmem, S, n, H, case):
    """The row-tiled form (H + 1 sweeps over tiles of rows, rho in HBM)
    agrees with the jnp oracle and a float64 SCD on the plain matrix:
    across a last tile shorter than the rest, zero rows past m, a
    revisited column, a zero column, and sweeps of one or two steps."""
    m = S * 128 - (300 if case.startswith("shorter") else 0)
    assert column_rows(m) == S and small_vmem._tile_rows(S) == _TILE
    rng = np.random.default_rng(S + n + H)
    A = rng.standard_normal((m, n)).astype(np.float32)
    idx = rng.integers(0, n, H).astype(np.int32)
    if case == "repeated indices":
        idx[::2] = idx[1]
    if case == "padded zero columns":
        A[:, -3:] = 0.0
        idx[::3] = n - 1
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


def test_resident_form_below_the_budget():
    """The kernel keeps rho and a column ring in VMEM while they fit:
    at the benchmark's 196,608 rows and epsilon's 400,000; HIGGS's
    11,000,000 rows take the row-tiled form, in tiles that fit."""
    from repro.kernels import scd as scd_mod

    assert scd_mod._tile_rows(column_rows(196_608)) is None
    assert scd_mod._tile_rows(column_rows(400_000)) is None
    T = scd_mod._tile_rows(column_rows(11_000_000))
    assert T and T % 8 == 0
    assert scd_mod._TILED_BUFFERS * T * 4 * 128 <= scd_mod.VMEM_BUDGET


def test_trainer_tall_problem_reaches_ridge_optimum(small_vmem, monkeypatch):
    """CoCoA through ``CoCoATrainer.run`` on a tall, skinny problem
    (40,000 x 28, K = 4, H = n_local = 7), the local solve in its
    row-tiled form: it certifies eps against the float64 normal
    equations' optimum, the primal it reports is the float64 primal at
    its alpha, and it follows the jnp solver's trajectory."""
    from repro.core import CoCoAConfig, CoCoATrainer

    monkeypatch.setattr(small_vmem, "VMEM_BUDGET",
                        small_vmem._TILED_BUFFERS * 128 * 4 * 128)
    m, n, K, eps = 40_000, 28, 4, 1e-3
    assert small_vmem._tile_rows(column_rows(m)) == 128
    rng = np.random.default_rng(0)
    F = rng.standard_normal((m, 4)) @ rng.standard_normal((4, n))
    A = (0.8 * rng.standard_normal((m, n)) + 0.5 * F).astype(np.float32)
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    b = np.sign(A @ rng.standard_normal(n)
                + rng.standard_normal(m)).astype(np.float32)
    A64, b64 = A.astype(np.float64), b.astype(np.float64)
    alpha_star = np.linalg.solve(A64.T @ A64 + np.eye(n), A64.T @ b64)

    def primal(a):
        return 0.5 * np.sum((A64 @ a - b64) ** 2) + 0.5 * a @ a

    p_star, p_zero = primal(alpha_star), primal(np.zeros(n))
    runs = {}
    for solver in ("scd_kernel", "scd_ref"):
        tr = CoCoATrainer(CoCoAConfig(K=K, H=n // K, lam=1.0, solver=solver,
                                      partitioner="block", seed=3), A, b)
        hist = tr.run(500, target_eps=eps, p_star=p_star)
        runs[solver] = hist, tr.alpha_final
    hist, alpha = runs["scd_kernel"]
    p64 = primal(alpha.astype(np.float64))
    # P(alpha) - p* = 1/2 ||alpha - alpha*||^2 in the metric A^T A + I,
    # so this bounds alpha's distance from the optimum as well
    assert hist.rounds[-1] < 500
    assert (p64 - p_star) / (p_zero - p_star) <= eps * 1.01
    assert abs(hist.primal[-1] - p64) / (p_zero - p_star) <= 1e-6
    hist_ref, alpha_ref = runs["scd_ref"]
    assert hist.rounds == hist_ref.rounds
    np.testing.assert_allclose(alpha, alpha_ref, rtol=1e-4, atol=1e-6)


def _stack(K, m, n, seed):
    """A random (K, n, column_rows(m), 128) stack, its (K, n) norms and
    (K, n) alphas, an m-vector w and K streams of coordinates."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, K * n)).astype(np.float32)
    packed, _ = pack_columns(A, block_partition(K * n, K))
    A_st = jnp.asarray(packed)
    alpha = jnp.asarray(rng.standard_normal((K, n)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal(m), jnp.float32)
    return A_st, column_norms(A_st), alpha, w, rng


def _assert_stack_reads_as_block(A_st, col_sq, alpha, w, rng, H):
    """For every worker k: the kernel given the whole stack and k gives
    what it gives on block k alone, bit for bit, and the oracle's
    result on block k."""
    kw = dict(sigma=4.0, lam=1.5, eta=0.6)
    for k in range(A_st.shape[0]):
        idx = jnp.asarray(rng.integers(0, A_st.shape[1], H), jnp.int32)
        dv_s, a_s = scd_steps_kernel(A_st, col_sq[k], alpha[k], w, idx,
                                     jnp.int32(k), **kw)
        dv_b, a_b = scd_steps_kernel(A_st[k], col_sq[k], alpha[k], w, idx,
                                     **kw)
        np.testing.assert_array_equal(dv_s, dv_b)
        np.testing.assert_array_equal(a_s, a_b)
        dv_r, a_r = scd_steps_ref(A_st[k], col_sq[k], alpha[k], w, idx,
                                  **kw)
        np.testing.assert_allclose(dv_s, dv_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(a_s, a_r, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,m,n,H", [
    (3, 200, 12, 40), (4, 384, 10, 2), (2, 130, 6, 30), (5, 1024, 8, 1)])
def test_kernel_reads_stack_at_worker(K, m, n, H):
    """The resident form, given the whole (K, n, S, 128) stack and a
    traced worker index k, fetches block k's columns from the stack:
    the same steps as on the sliced block, for every k, including H
    shorter than the fetch ring and H = 1."""
    A_st, col_sq, alpha, w, rng = _stack(K, m, n, seed=K + m + n + H)
    _assert_stack_reads_as_block(A_st, col_sq, alpha, w, rng, H)


@pytest.mark.parametrize("K,S,n,H", [
    (3, 40, 9, 13), (2, 48, 5, 2), (4, 40, 5, 1)])
def test_row_tiled_kernel_reads_stack_at_worker(small_vmem, K, S, n, H):
    """The row-tiled form reads block k's columns from the stack in its
    sweeps, with a last tile shorter than the rest (S = 40) or none
    (S = 48): the same steps as on the sliced block, for every k."""
    m = S * 128 - 300
    assert column_rows(m) == S and small_vmem._tile_rows(S) == _TILE
    A_st, col_sq, alpha, w, rng = _stack(K, m, n, seed=K + S + n + H)
    _assert_stack_reads_as_block(A_st, col_sq, alpha, w, rng, H)


@pytest.mark.parametrize("form,exchange", [
    ("resident", "persistent"),
    ("resident", "compressed:ef:int4/stale:k=1"),
    ("row-tiled", "persistent")])
def test_trainer_stack_path_matches_slice_path(request, monkeypatch, form,
                                               exchange):
    """``CoCoATrainer.run`` on the kernel path, whose ``lax.map`` hands
    each worker's kernel the whole stack and its index, records the
    history and returns the alpha of the same run through a per-worker
    slice of the stack (the driver's path for an algorithm without
    ``local_step_at``), bit for bit, and follows ``scd_ref``'s
    trajectory."""
    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.core import cocoa
    from repro.data import make_glm_data

    m, n, K = (4_800, 24, 3) if form == "row-tiled" else (200, 96, 4)
    if form == "row-tiled":
        assert request.getfixturevalue("small_vmem")._tile_rows(
            column_rows(m)) == _TILE
    A, b, _ = make_glm_data(m=m, n=n, density=0.5, seed=7)

    def run(solver):
        cfg = CoCoAConfig(K=K, H=n // K, solver=solver, exchange=exchange,
                          partitioner="block", seed=5)
        tr = CoCoATrainer(cfg, A, b)
        return tr.run(rounds=8), tr.alpha_final

    hist, alpha = run("scd_kernel")
    with monkeypatch.context() as mp:
        mp.delattr(cocoa._CoCoARound, "local_step_at")
        hist_sl, alpha_sl = run("scd_kernel")
    assert hist.rounds == hist_sl.rounds == list(range(1, 9))
    assert hist.primal == hist_sl.primal
    np.testing.assert_array_equal(alpha, alpha_sl)
    hist_ref, alpha_ref = run("scd_ref")
    np.testing.assert_allclose(hist.primal, hist_ref.primal, rtol=1e-4)
    np.testing.assert_allclose(alpha, alpha_ref, rtol=1e-4, atol=1e-6)
