"""CoCoA core: convergence, equivalence, partitioning, baselines."""
import numpy as np
import pytest

from repro.core import COMM_SCHEMES, CoCoAConfig, CoCoATrainer
from repro.core.baselines import MinibatchSCD, MinibatchSGD, SGDConfig
from repro.core.glm import GLMProblem, optimal_objective, primal_objective, ridge_exact
from repro.core import partition as pt
from repro.data import make_glm_data

import jax.numpy as jnp


@pytest.fixture(scope="module")
def problem_data():
    return make_glm_data(m=256, n=512, density=0.25, seed=3)


def test_cocoa_converges_to_ridge_solution(problem_data):
    A, b, _ = problem_data
    cfg = CoCoAConfig(K=8, H=256, lam=1.0, eta=1.0)
    tr = CoCoATrainer(cfg, A, b)
    hist = tr.run(rounds=80, record_every=5, target_eps=1e-6)
    assert hist.subopt[-1] <= 1e-6
    alpha_star = ridge_exact(A, b, 1.0)
    rel = np.linalg.norm(tr.alpha_final - alpha_star) / np.linalg.norm(alpha_star)
    assert rel < 5e-3


def test_cocoa_elastic_net_converges(problem_data):
    A, b, _ = problem_data
    cfg = CoCoAConfig(K=4, H=256, lam=2.0, eta=0.5)
    tr = CoCoATrainer(cfg, A, b)
    hist = tr.run(rounds=150, record_every=10, target_eps=1e-4)
    assert hist.subopt[-1] <= 1e-4
    # sparsity from the l1 part
    assert (np.abs(tr.alpha_final) < 1e-8).mean() > 0.05


def test_suboptimality_monotone_trend(problem_data):
    A, b, _ = problem_data
    tr = CoCoATrainer(CoCoAConfig(K=8, H=128), A, b)
    hist = tr.run(rounds=40, record_every=1)
    s = np.array(hist.subopt)
    # overall decreasing (allow tiny numeric jitter)
    assert s[-1] < s[0] * 1e-1
    assert np.all(s[1:] <= s[:-1] + 1e-6)


def test_larger_H_fewer_rounds(problem_data):
    A, b, _ = problem_data
    rounds = {}
    for H in (32, 512):
        tr = CoCoATrainer(CoCoAConfig(K=8, H=H, seed=1), A, b)
        hist = tr.run(rounds=400, record_every=1, target_eps=1e-3)
        rounds[H] = hist.rounds_to(1e-3)
    assert rounds[512] is not None and rounds[32] is not None
    assert rounds[512] < rounds[32]


def test_minibatch_scd_slower_than_cocoa(problem_data):
    """CoCoA's immediate local updates beat fixed-residual mini-batch SCD
    round-for-round (the paper's motivation for choosing CoCoA)."""
    A, b, _ = problem_data
    coc = CoCoATrainer(CoCoAConfig(K=8, H=256, solver="scd_ref"), A, b)
    mb = CoCoATrainer(CoCoAConfig(K=8, H=256, solver="scd_fixed"), A, b)
    h1 = coc.run(rounds=60, record_every=60)
    h2 = mb.run(rounds=60, record_every=60)
    assert h1.subopt[-1] < h2.subopt[-1]


def test_minibatch_scd_first_class_converges(problem_data):
    """MinibatchSCD forces the fixed-residual solver and, with the
    1/sigma damping applied consistently to alpha AND Delta v, actually
    converges to the ridge solution (slower than CoCoA, but it gets
    there — the §2.1 baseline is a real algorithm, not a strawman)."""
    A, b, _ = problem_data
    mb = MinibatchSCD(CoCoAConfig(K=8, H=256, solver="scd_ref"), A, b)
    assert mb.cfg.solver == "scd_fixed"  # promoted, not trusted
    hist = mb.run(rounds=300, record_every=10, target_eps=1e-3)
    assert hist.subopt[-1] <= 1e-3
    # the residual invariant w = A alpha - b survives the damping:
    # recomputing the objective from alpha_final matches the trace
    assert abs(mb.objective_of(mb.alpha_final) - hist.primal[-1]) < 1e-2


def test_config_rejects_unknown_comm_scheme():
    """A typo'd scheme must raise, not silently run persistent."""
    with pytest.raises(ValueError, match="unknown exchange spec segment"):
        CoCoAConfig(exchange="persistant")
    with pytest.raises(ValueError, match="unknown exchange spec segment"):
        SGDConfig(exchange="spark")
    for scheme in COMM_SCHEMES:  # the real set all validate
        CoCoAConfig(exchange=scheme)
    # the deprecated comm_scheme= spelling still works — under a warning
    with pytest.warns(DeprecationWarning, match="comm_scheme"):
        cfg = CoCoAConfig(comm_scheme="compressed")
    assert cfg.exchange.scheme.codec.name == "int8"
    # and a typo through the deprecated spelling still raises
    with pytest.raises(ValueError, match="unknown comm scheme"):
        CoCoAConfig(comm_scheme="persistant")


def test_comm_bytes_match_scheme_dtypes(problem_data):
    """Modelled per-round traffic is sized to the dtypes the collectives
    move: f32 updates (4B) for persistent/spark_faithful, int8 + a
    4-byte scale for compressed; spark_faithful adds the alpha blocks."""
    A, b, _ = problem_data
    m, n, K = A.shape[0], A.shape[1], 8
    by = {s: CoCoATrainer(CoCoAConfig(K=K, exchange=s), A, b)
          for s in COMM_SCHEMES}
    n_pad = by["persistent"].part.n_padded
    assert by["persistent"].comm_bytes_per_round() == 2 * K * m * 4
    assert (by["spark_faithful"].comm_bytes_per_round()
            == 2 * K * m * 4 + 2 * K * n_pad * 4)
    assert by["compressed"].comm_bytes_per_round() == 2 * K * (m + 4)
    # codec-composed schemes: the transport is priced per wire codec
    int4 = CoCoATrainer(CoCoAConfig(K=K, exchange="compressed:int4"),
                        A, b)
    assert int4.comm_bytes_per_round() == 2 * K * (-(-m // 2) + 4)
    sgd = {s: MinibatchSGD(SGDConfig(K=K, exchange=s), A, b)
           for s in COMM_SCHEMES}
    assert sgd["persistent"].comm_bytes_per_round() == 2 * K * n * 4
    assert sgd["compressed"].comm_bytes_per_round() == 2 * K * (n + 4)


def test_mllib_style_sgd_much_slower(problem_data):
    A, b, _ = problem_data
    p_star = optimal_objective(GLMProblem(1.0, 1.0), A, b)
    tr = CoCoATrainer(CoCoAConfig(K=8, H=256), A, b)
    hist = tr.run(rounds=40, record_every=40)
    sgd = MinibatchSGD(SGDConfig(batch_frac=0.5, step_size=1e-3, lam=1.0), A, b)
    hist2 = sgd.run(40, p_star=p_star, p_zero=tr.p_zero, record_every=40)
    assert hist.subopt[-1] < hist2.subopt[-1]


def test_balanced_partitioner_beats_block():
    A, b, _ = make_glm_data(m=128, n=400, density=0.15, zipf_a=1.05, seed=7)
    nnz = (np.abs(A) > 0).sum(axis=0)
    bal = pt.balanced_partition(nnz, 8)
    blk = pt.block_partition(400, 8)
    assert pt.partition_imbalance(bal, nnz) <= pt.partition_imbalance(blk, nnz)
    assert pt.partition_imbalance(bal, nnz) < 1.05


def test_pack_unpack_roundtrip():
    A, b, _ = make_glm_data(m=64, n=100, seed=0)
    part = pt.balanced_partition((np.abs(A) > 0).sum(0), 4)
    packed, mask = pt.pack_columns(A, part)
    assert packed.shape[0] == 4 and mask.shape == packed.shape[:2]
    # scatter alpha back
    alpha_st = np.arange(4 * part.n_padded, dtype=np.float32).reshape(4, -1)
    alpha_st *= mask
    alpha = pt.unpack_alpha(alpha_st, part, 100)
    for k, ids in enumerate(part.owned):
        np.testing.assert_allclose(alpha[ids], alpha_st[k, : len(ids)])


def test_objective_primal_from_state_matches(problem_data):
    A, b, _ = problem_data
    from repro.core.glm import primal_from_state
    prob = GLMProblem(1.0, 0.7)
    alpha = np.random.default_rng(0).standard_normal(A.shape[1]).astype(np.float32)
    w = A @ alpha - b
    p1 = primal_objective(prob, jnp.asarray(A), jnp.asarray(b), jnp.asarray(alpha))
    p2 = primal_from_state(prob, jnp.asarray(w), prob.regularizer(jnp.asarray(alpha)))
    assert abs(float(p1) - float(p2)) < 1e-2


def test_compressed_communication_converges(problem_data):
    """Beyond-paper: int8-quantized Delta-v exchange (4x less traffic)
    must not break CoCoA's convergence (inexact local solutions are
    within the framework's tolerance)."""
    A, b, _ = problem_data
    tr = CoCoATrainer(CoCoAConfig(K=8, H=256, exchange="compressed"),
                      A, b)
    hist = tr.run(rounds=120, record_every=10, target_eps=1e-3)
    assert hist.subopt[-1] <= 1e-3
