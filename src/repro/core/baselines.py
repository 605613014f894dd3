"""Baselines the paper compares against — as first-class algorithms on
the unified distributed-driver layer (``repro.core.distributed``).

* Mini-batch SCD (SDCA-style, no immediate local updates) —
  :class:`MinibatchSCD`: identical partitioning, drivers and comm
  schemes to CoCoA, but every local step sees the round-start residual
  and aggregation is damped by 1/sigma. (Paper §2/§2.1.)

* Mini-batch SGD — :class:`MinibatchSGD`, the MLlib
  ``LinearRegressionWithSGD`` stand-in (paper §5.4, Fig 5): row-sampled
  gradient steps on the primal with a 1/sqrt(t) step-size schedule.
  ``run()`` is the legacy single-device loop; ``run_workers()`` /
  ``run_sharded()`` are the distributed drivers with row-partitioned
  data and an n-dimensional gradient all-reduce — note this is *more*
  traffic than CoCoA's m-vector whenever n > m, one of the reasons
  CoCoA wins (§5.4), and it is visible in the sharded HLO.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import distributed as dist
from repro.core.glm import GLMProblem, optimal_objective, primal_objective, suboptimality
from repro.core.cocoa import CoCoAConfig, CoCoATrainer, History, record_loop
from repro.utils import compat


class MinibatchSCD(CoCoATrainer):
    """First-class mini-batch SCD (the paper's §2.1 baseline).

    CoCoA's partitioning, both execution drivers, and all three comm
    schemes — with the fixed-residual local solver and 1/sigma-damped
    aggregation. Constructing one forces ``solver="scd_fixed"`` so the
    baseline cannot silently run CoCoA's immediate-local-update solver.
    """

    def __init__(self, cfg: CoCoAConfig, A: np.ndarray, b: np.ndarray):
        if cfg.solver != "scd_fixed":
            cfg = dataclasses.replace(cfg, solver="scd_fixed")
        super().__init__(cfg, A, b)


@dataclass(frozen=True)
class SGDConfig:
    batch_frac: float = 1.0          # MLlib miniBatchFraction
    step_size: float = 1.0           # base step (gamma / sqrt(t) schedule)
    lam: float = 1.0
    eta: float = 1.0
    K: int = 8
    H: int = 1                       # local SGD steps per round (H=1: MLlib)
    seed: int = 0
    # the unified exchange surface (see distributed.ExchangeConfig for
    # the spec grammar); the string knobs below are deprecated aliases
    exchange: "dist.ExchangeConfig | str | None" = None
    comm_scheme: str | None = None   # DEPRECATED alias -> exchange
    exchange_mode: str | None = None  # DEPRECATED alias -> exchange

    def __post_init__(self):
        # fold everything into ONE validated ExchangeConfig (fail loudly
        # on typos) and store the canonical values back so
        # dataclasses.replace(cfg, ...) round-trips silently
        ex = dist.resolve_exchange(self.exchange,
                                   comm_scheme=self.comm_scheme,
                                   exchange_mode=self.exchange_mode,
                                   owner=type(self).__name__)
        object.__setattr__(self, "exchange", ex)
        object.__setattr__(self, "comm_scheme", ex.scheme.name)
        object.__setattr__(self, "exchange_mode", ex.mode.spec)
        if self.H < 1:
            raise ValueError(f"H must be >= 1, got {self.H}")


class _SGDRound:
    """Mini-batch SGD's plug into the generic round drivers: each worker
    owns a row block, samples a local mini-batch, and contributes an
    n-dimensional partial gradient to the all-reduce; the step-size
    schedule and the l1 proximal step run on the aggregated gradient.

    With ``H > 1`` the round is *local SGD* (the local-updates line the
    paper's trade-off generalizes to): each worker takes H proximal
    steps on a private model copy — its partial gradient scaled by K
    stands in for the full gradient — and the all-reduced quantity is
    the model delta, averaged by ``apply_update``. ``H=1`` keeps the
    exact MLlib-style single aggregated step (bit-identical RNG and
    float order), so the default path is unchanged."""

    # SGD's aggregate is a MEAN over workers (the /K in apply_update for
    # local SGD, the full-gradient estimate for H=1), so under elastic
    # membership the drivers rescale the summed update by K / K_live —
    # the average over the workers that actually contributed. (CoCoA's
    # aggregate is an unscaled SUM of residual deltas; rescaling it
    # would break the w = A@alpha - b invariant, so _CoCoARound leaves
    # this flag unset.)
    live_reweight = True

    def __init__(self, cfg: SGDConfig, problem: GLMProblem,
                 m_local: int, batch_local: int):
        self.cfg, self.problem = cfg, problem
        self.m_local, self.batch_local = m_local, batch_local
        self.scale = m_local / batch_local

    def _partial_grad(self, A_k, b_k, alpha, key):
        rows = jax.random.choice(key, self.m_local,
                                 shape=(self.batch_local,), replace=False)
        A_s, b_s = A_k[rows], b_k[rows]
        resid = A_s @ alpha - b_s
        return (A_s.T @ resid) * self.scale

    def _prox_step(self, alpha, grad, lr):
        alpha_new = alpha - lr * grad
        # L1 proximal step for the elastic-net case.
        thresh = lr * self.cfg.lam * (1.0 - self.cfg.eta)
        return jnp.sign(alpha_new) * jnp.maximum(
            jnp.abs(alpha_new) - thresh, 0.0)

    def local_step(self, data_k, local_k, alpha, key, t):
        cfg = self.cfg
        A_k, b_k = data_k                 # (m_local, n), (m_local,)
        if cfg.H == 1:
            return self._partial_grad(A_k, b_k, alpha, key), local_k
        lr = cfg.step_size / jnp.sqrt(jnp.asarray(t, jnp.float32))

        def body(alpha_loc, key_h):
            # K x the partial gradient ~= the full gradient from this
            # worker's rows alone (exact in expectation under uniform
            # row partitioning)
            g = (cfg.K * self._partial_grad(A_k, b_k, alpha_loc, key_h)
                 + cfg.lam * cfg.eta * alpha_loc)
            return self._prox_step(alpha_loc, g, lr), None

        alpha_H, _ = jax.lax.scan(body, alpha,
                                  jax.random.split(key, cfg.H))
        return alpha_H - alpha, local_k

    def apply_update(self, alpha, total, t):
        cfg = self.cfg
        if cfg.H > 1:
            # total is the summed model delta: average the H-step local
            # models (the classic local-SGD combiner)
            return alpha + total / cfg.K
        grad = total + cfg.lam * cfg.eta * alpha
        lr = cfg.step_size / jnp.sqrt(jnp.asarray(t, jnp.float32))
        return self._prox_step(alpha, grad, lr)

    def local_metric(self, data_k, local_k, alpha_new):
        A_k, b_k = data_k                 # zero-padded rows contribute 0
        r = A_k @ alpha_new - b_k
        return 0.5 * jnp.sum(r * r)

    def finalize_metric(self, alpha_new, loss_sum):
        return loss_sum + self.problem.regularizer(alpha_new)


class MinibatchSGD:
    """MLlib-style distributed mini-batch SGD for elastic-net regression."""

    def __init__(self, cfg: SGDConfig, A: np.ndarray, b: np.ndarray):
        self.cfg = cfg
        self.A_np = np.asarray(A, np.float32)
        self.b_np = np.asarray(b, np.float32)
        self.A = jnp.asarray(self.A_np)
        self.b = jnp.asarray(self.b_np)
        self.m, self.n = A.shape
        self.problem = GLMProblem(lam=cfg.lam, eta=cfg.eta)
        self.exchange = cfg.exchange
        self.scheme = self.exchange.scheme
        self.mode = self.exchange.mode
        self.batch = max(1, int(cfg.batch_frac * self.m))
        self._step = self._build_step()
        self.m_local = -(-self.m // cfg.K)
        self.batch_local = max(1, int(round(cfg.batch_frac * self.m_local)))
        self._dist_state = None  # (data, algo, round_fn), built lazily
        self._p_star_cache: float | None = None

    def _distributed(self):
        """Row partition + round drivers, built on first use: the legacy
        single-device ``run()`` path must not pay for a second padded
        copy of A it never touches."""
        if self._dist_state is None:
            cfg, m_local = self.cfg, self.m_local
            # K zero-padded row blocks (padded rows are all-zero in A
            # and b, so they add 0 to both the gradient and the loss)
            A_pad = np.zeros((m_local * cfg.K, self.n), np.float32)
            A_pad[: self.m] = np.asarray(self.A, np.float32)
            b_pad = np.zeros((m_local * cfg.K,), np.float32)
            b_pad[: self.m] = np.asarray(self.b, np.float32)
            data = (jnp.asarray(A_pad.reshape(cfg.K, m_local, self.n)),
                    jnp.asarray(b_pad.reshape(cfg.K, m_local)))
            algo = _SGDRound(cfg, self.problem, m_local, self.batch_local)
            round_fn = dist.build_virtual_round(algo, self.exchange, data,
                                                K=cfg.K)
            self._dist_state = (data, algo, round_fn)
        return self._dist_state

    @property
    def _data(self):
        return self._distributed()[0]

    @property
    def _algo(self):
        return self._distributed()[1]

    @property
    def _round_fn(self):
        return self._distributed()[2]

    # ------------------------------------------------------------------
    @property
    def p_star(self) -> float:
        if self._p_star_cache is None:
            self._p_star_cache = optimal_objective(
                self.problem, np.asarray(self.A), np.asarray(self.b))
        return self._p_star_cache

    @property
    def p_zero(self) -> float:
        return float(self.problem.loss(-self.b))

    def init_state(self):
        """(local, shared) for the distributed drivers: SGD keeps no
        per-worker persistent state, so ``local`` is an empty block
        (widened with the per-worker residual over the n-length
        gradient under a stateful ``ef:`` codec). Stale mode widens the
        shared slot to (alpha, pending gradient)."""
        local = jnp.zeros((self.cfg.K, 0), jnp.float32)
        local = dist.wrap_local_state(self.exchange, local, self.n,
                                      self.cfg.K)
        alpha = jnp.zeros(self.n, jnp.float32)
        return local, dist.init_exchange_state(self.exchange, alpha)

    def with_H(self, H: int) -> "MinibatchSGD":
        """Fresh trainer with the local-update count moved (the H-sweep
        clone hook shared with the CoCoA-family trainers)."""
        return type(self)(dataclasses.replace(self.cfg, H=int(H)),
                          self.A_np, self.b_np)

    def comm_bytes_per_round(self, t: int | None = None) -> int:
        """Modelled bytes through the master per round: the n-vector
        gradient all-reduce + parameter broadcast across K workers,
        sized to the dtypes the collectives actually move (int8 gradient
        + f32 scale under ``compressed``, f32 otherwise). ``t`` asks for
        a specific 1-based round under the elastic membership schedule
        (dropped workers ship nothing; ``None`` = all K live)."""
        K_live = (None if t is None
                  else self.exchange.membership.live_count(t, self.cfg.K))
        return self.scheme.bytes_per_round(self.n, self.cfg.K,
                                           K_live=K_live,
                                           backend=self.exchange.backend)

    # ------------------------------------------------------------------
    # legacy single-device loop (global row sampling)
    # ------------------------------------------------------------------
    def _build_step(self):
        cfg, batch = self.cfg, self.batch

        @jax.jit
        def step(A, b, alpha, t, key):
            rows = jax.random.choice(key, A.shape[0], shape=(batch,),
                                     replace=False)
            A_s, b_s = A[rows], b[rows]
            resid = A_s @ alpha - b_s
            grad = (A_s.T @ resid) * (self.m / batch) + cfg.lam * cfg.eta * alpha
            lr = cfg.step_size / jnp.sqrt(t.astype(jnp.float32))
            alpha_new = alpha - lr * grad
            # L1 proximal step for the elastic-net case.
            thresh = lr * cfg.lam * (1.0 - cfg.eta)
            alpha_new = jnp.sign(alpha_new) * jnp.maximum(
                jnp.abs(alpha_new) - thresh, 0.0)
            return alpha_new

        return step

    def run(self, rounds: int, p_star: float | None = None,
            p_zero: float | None = None, record_every: int = 10,
            target_eps: float | None = None) -> History:
        if self.mode.stale:
            # the legacy single-device loop has no exchange to delay;
            # silently running it synchronously would mislabel the
            # trajectory (the knob must fail loudly, like a typo'd
            # scheme would)
            raise ValueError(
                "exchange_mode='stale' has no meaning for the legacy "
                "single-device run(); use run_workers() or run_sharded()")
        p_star = self.p_star if p_star is None else p_star
        p_zero = self.p_zero if p_zero is None else p_zero
        alpha = jnp.zeros(self.n, jnp.float32)
        key = jax.random.key(self.cfg.seed)
        hist = History(p_star=p_star, p_zero=p_zero)
        for t in range(1, rounds + 1):
            key, sub = jax.random.split(key)
            alpha = self._step(self.A, self.b, alpha, jnp.asarray(t), sub)
            if t % record_every == 0 or t == rounds:
                p = float(primal_objective(self.problem, self.A, self.b, alpha))
                hist.rounds.append(t)
                hist.primal.append(p)
                s = suboptimality(p, p_star, p_zero)
                hist.subopt.append(s)
                if target_eps is not None and s <= target_eps:
                    break
        self.alpha_final = np.asarray(alpha)
        return hist

    # ------------------------------------------------------------------
    # distributed drivers (row-partitioned, per-worker sampling)
    # ------------------------------------------------------------------
    def _start(self, p_star, p_zero):
        """The history a call records into and its round-key chain."""
        hist = History(p_star=self.p_star if p_star is None else p_star,
                       p_zero=self.p_zero if p_zero is None else p_zero)
        return hist, jax.random.key(self.cfg.seed)

    def _keep_final(self, local, alpha):
        self.alpha_final = np.asarray(alpha)

    def run_workers(self, rounds: int, record_every: int = 10,
                    target_eps: float | None = None,
                    p_star: float | None = None,
                    p_zero: float | None = None) -> History:
        """K virtual workers (vmap over the worker axis) — same math as
        ``run_sharded`` with the communication mechanics elided."""
        with jax.profiler.TraceAnnotation("repro.setup"):
            local, alpha = self.init_state()
            hist, key = self._start(p_star, p_zero)
        return record_loop(self._round_fn, local, alpha, hist, key, rounds,
                           record_every, target_eps, self._keep_final)

    def build_sharded_round(self, mesh: Mesh):
        """Distributed round via the generic shard_map driver; K must
        equal the mesh axis size. Returns jitted
        ``round_fn(local, alpha, key, t)``."""
        assert mesh.devices.size == self.cfg.K, (mesh.devices.size, self.cfg.K)
        return dist.build_sharded_round(self._algo, self.exchange,
                                        dist.place_data(mesh, self._data),
                                        mesh)

    def run_sharded(self, rounds: int, mesh: Mesh | None = None,
                    record_every: int = 10,
                    target_eps: float | None = None,
                    p_star: float | None = None,
                    p_zero: float | None = None) -> History:
        with jax.profiler.TraceAnnotation("repro.setup"):
            if mesh is None:
                mesh = compat.make_mesh((self.cfg.K,), ("workers",))
            round_fn = self.build_sharded_round(mesh)
            local, alpha = dist.place_state(mesh, *self.init_state())
            hist, key = self._start(p_star, p_zero)
        return record_loop(round_fn, local, alpha, hist, key, rounds,
                           record_every, target_eps, self._keep_final)
