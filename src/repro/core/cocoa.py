"""CoCoA: communication-efficient distributed primal-dual GLM training.

Two execution drivers over identical math, both built on the unified
distributed-driver layer (``repro.core.distributed``):

  * ``CoCoATrainer.run()`` — K *virtual* workers on however many real
    devices exist (vmap over the worker axis). Used for convergence
    studies and the paper-figure benchmarks on CPU.
  * ``CoCoATrainer.run_sharded()`` — real distribution via ``shard_map``
    over a 1-D ``workers`` mesh axis with an explicit all-reduce of the
    m-dimensional update Delta v (the paper's AllReduce pattern, Fig 1).

Communication schemes (the paper's §5.3 plus one beyond-paper variant;
see ``distributed.CommScheme`` for the mechanics and byte accounting):

  * ``persistent``      — alpha_[k] lives on its worker across rounds
    (the paper's "persistent local memory" / (B)*, (D)* optimization;
    on TPU this is simply donated device-resident state).
  * ``spark_faithful``  — everything is shipped through the master every
    round: Delta v is collected (all-gather) and summed locally, and
    alpha is all-gathered with each worker re-slicing its own block.
    Mathematically the identity, but the extra collective traffic is
    real and visible in the HLO (and is charged by the overhead model).
  * ``compressed``      — int8-quantized Delta v exchange (4x less
    traffic than f32) through the one shared quantizer in
    ``distributed.quantize_update``.
  * ``reduce_scatter``  — the Delta v exchange as an explicit
    ``psum_scatter`` + ``all_gather`` ring pair: 2*(K-1)/K of the
    vector per worker each way, the cheapest exact f32 exchange.

Orthogonal to the scheme, ``exchange_mode`` picks the staleness regime
(``distributed.ExchangeMode``): ``sync`` applies the round's aggregate
immediately; ``stale`` applies it one round late (workers compute
against the unapplied residual — the paper's §4-§5 Spark
scheduling-delay regime as an explicit knob), with the final pending
Delta v flushed after the last round so nothing is dropped.

Mini-batch SCD (the paper's §2.1 baseline) runs the same drivers with
the fixed-residual solver — see ``repro.core.baselines.MinibatchSCD``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import distributed as dist
from repro.core import partition as part_mod
from repro.core import solvers
from repro.core.glm import GLMProblem, optimal_objective, primal_objective, suboptimality
from repro.utils import compat


@dataclass(frozen=True)
class CoCoAConfig:
    K: int = 8                       # number of workers
    H: int = 256                     # local SCD steps per round
    lam: float = 1.0
    eta: float = 1.0                 # 1.0 = ridge
    sigma: float | None = None       # subproblem safety; default K ("adding")
    solver: str = "scd_ref"          # scd_ref | scd_kernel | scd_fixed
    # the unified exchange surface: an ExchangeConfig or a spec string
    # like "compressed:int4/stale:k=2/drop:1@5" (see
    # distributed.ExchangeConfig for the grammar); None means the
    # default persistent/sync exchange unless the deprecated knobs below
    # say otherwise
    exchange: "dist.ExchangeConfig | str | None" = None
    comm_scheme: str | None = None   # DEPRECATED alias -> exchange
    exchange_mode: str | None = None  # DEPRECATED alias -> exchange
    partitioner: str = "balanced"    # balanced | block
    seed: int = 0

    def __post_init__(self):
        # fold the deprecated comm_scheme/exchange_mode strings and the
        # unified spec into ONE validated ExchangeConfig (a typo'd
        # scheme or mode must fail loudly, not silently fall through to
        # persistent/synchronous behavior), then store the canonical
        # values back so dataclasses.replace(cfg, ...) round-trips
        # silently and reads of the legacy fields stay truthful
        ex = dist.resolve_exchange(self.exchange,
                                   comm_scheme=self.comm_scheme,
                                   exchange_mode=self.exchange_mode,
                                   owner=type(self).__name__)
        object.__setattr__(self, "exchange", ex)
        object.__setattr__(self, "comm_scheme", ex.scheme.name)
        object.__setattr__(self, "exchange_mode", ex.mode.spec)
        if self.partitioner not in ("balanced", "block"):
            raise ValueError(f"unknown partitioner {self.partitioner!r}; "
                             f"known: ('balanced', 'block')")

    @property
    def sigma_val(self) -> float:
        return float(self.K if self.sigma is None else self.sigma)


@dataclass
class History:
    rounds: list = field(default_factory=list)
    primal: list = field(default_factory=list)
    subopt: list = field(default_factory=list)
    p_star: float = float("nan")
    p_zero: float = float("nan")

    def rounds_to(self, eps: float) -> int | None:
        for r, s in zip(self.rounds, self.subopt):
            if s <= eps:
                return r
        return None


def _get_solver(name: str) -> Callable:
    if name == "scd_ref":
        return solvers.scd_steps
    if name == "scd_fixed":
        return solvers.scd_steps_fixed_point
    if name == "scd_kernel":
        from repro.kernels import ops as kops
        return kops.scd_steps_kernel
    raise ValueError(f"unknown local solver {name!r}")


class _CoCoARound:
    """CoCoA's plug into the generic round drivers: the local SCD solve,
    the residual update ``w += sum_k Delta v_k``, and the primal metric
    evaluated without gathering alpha (``loss(w) + psum(reg_k)``).

    Mini-batch SCD rides the same adapter: with ``solver="scd_fixed"``
    the aggregation is damped by 1/sigma (paper §2.1) — in ONE place, so
    the virtual and sharded paths cannot disagree about it.
    """

    def __init__(self, cfg: CoCoAConfig, problem: GLMProblem,
                 solver: Callable):
        self.cfg, self.problem, self.solver = cfg, problem, solver

    def local_step(self, data_k, alpha_k, w, key, t):
        cfg = self.cfg
        A_k, col_sq_k, mask_k = data_k
        logits = jnp.where(mask_k > 0, 0.0, -jnp.inf)
        idx = jax.random.categorical(key, logits,
                                     shape=(cfg.H,)).astype(jnp.int32)
        dv, alpha_new = self.solver(A_k, col_sq_k, alpha_k, w, idx,
                                    sigma=cfg.sigma_val, lam=cfg.lam,
                                    eta=cfg.eta)
        if cfg.solver == "scd_fixed":
            # damped mini-batch aggregation: scale BOTH the local alpha
            # move and Delta v by 1/sigma so the shared-residual
            # invariant w = A alpha - b survives the round (damping only
            # dv silently de-synced alpha from w).
            alpha_new = alpha_k + (alpha_new - alpha_k) / cfg.sigma_val
            dv = dv / cfg.sigma_val
        return dv, alpha_new

    def apply_update(self, w, total_dv, t):
        return w + total_dv

    def local_metric(self, data_k, alpha_k, w_new):
        _, _, mask_k = data_k
        return self.problem.regularizer(alpha_k * mask_k)

    def finalize_metric(self, w_new, reg_sum):
        return self.problem.loss(w_new) + reg_sum


def record_loop(round_fn, local, shared, hist: History, key, rounds: int,
                record_every: int, target_eps: float | None,
                keep: Callable) -> History:
    """The record loop every trainer's entry points share.

    Runs ``round_fn(local, shared, key, t)`` for ``t = 1 .. rounds``,
    reads the round's metric back every ``record_every`` rounds (and
    after the last) into ``hist``, and stops once its suboptimality is
    at or below ``target_eps``. A stale run's pending aggregate is then
    absorbed (``finish_run``) and ``keep(local, shared)`` copies the
    final iterate to the host.

    Profiling a call (``jax.profiler.trace``) shows these phases as
    host spans on the device ops' clock: per round a
    ``repro.round`` step (``step_num = t``) holding ``repro.dispatch``
    (the key split and the round's enqueue; on a fresh program its
    trace, lowering and compile) and ``repro.readback`` (the metric's
    sync and the stop test), then ``repro.finish``. With no profiler
    running they record nothing.
    """
    last_t = 0
    for t in range(1, rounds + 1):
        with jax.profiler.StepTraceAnnotation("repro.round", step_num=t):
            with jax.profiler.TraceAnnotation("repro.dispatch"):
                key, sub = jax.random.split(key)
                local, shared, primal = round_fn(local, shared, sub, t)
            last_t = t
            if t % record_every == 0 or t == rounds:
                with jax.profiler.TraceAnnotation("repro.readback"):
                    p = float(primal)
                    s = suboptimality(p, hist.p_star, hist.p_zero)
                    hist.rounds.append(t)
                    hist.primal.append(p)
                    hist.subopt.append(s)
                    if target_eps is not None and s <= target_eps:
                        break
    with jax.profiler.TraceAnnotation("repro.finish"):
        # stale runs carry one unapplied aggregate; absorb it so the
        # final iterate reflects every round that was computed
        keep(local, dist.finish_run(round_fn, shared, last_t))
    return hist


class CoCoATrainer:
    """Owns the partitioned data and the jitted round functions."""

    def __init__(self, cfg: CoCoAConfig, A: np.ndarray, b: np.ndarray):
        self.cfg = cfg
        self.problem = GLMProblem(lam=cfg.lam, eta=cfg.eta)
        self.exchange = cfg.exchange
        self.scheme = self.exchange.scheme
        self.mode = self.exchange.mode
        self.A_np, self.b_np = np.asarray(A, np.float32), np.asarray(b, np.float32)
        m, n = A.shape
        self.m, self.n = m, n
        nnz = (np.abs(self.A_np) > 0).sum(axis=0)
        if cfg.partitioner == "balanced":
            self.part = part_mod.balanced_partition(nnz, cfg.K)
        else:
            self.part = part_mod.block_partition(n, cfg.K)
        A_st, mask = part_mod.pack_columns(self.A_np, self.part)
        # uploaded in the layout the solvers read: (K, n_pad, S, 128)
        self.A_st = jnp.asarray(A_st)
        del A_st
        self.col_sq = part_mod.column_norms(self.A_st)
        self.mask = jnp.asarray(mask)                       # (K, n_pad)
        self.b = jnp.asarray(self.b_np)
        self._solver = _get_solver(cfg.solver)
        self._algo = _CoCoARound(cfg, self.problem, self._solver)
        self._data = (self.A_st, self.col_sq, self.mask)
        self._round_fn = dist.build_virtual_round(
            self._algo, self.exchange, self._data, K=cfg.K,
            use_map=(cfg.solver == "scd_kernel"))  # pallas interpret: no vmap
        self._p_star_cache: float | None = None

    @property
    def p_star(self) -> float:
        if self._p_star_cache is None:
            self._p_star_cache = optimal_objective(self.problem, self.A_np, self.b_np)
        return self._p_star_cache

    @property
    def p_zero(self) -> float:
        return float(self.problem.loss(-self.b))

    def init_state(self):
        alpha = jnp.zeros((self.cfg.K, self.part.n_padded), jnp.float32)
        w = -self.b  # w = A @ 0 - b
        # stale mode widens the shared slot to (w, pending Delta v
        # queue); a stateful (ef:) codec widens the local slot to
        # (alpha, per-worker residual over the m-length Delta v)
        alpha = dist.wrap_local_state(self.exchange, alpha, self.m,
                                      self.cfg.K)
        return alpha, dist.init_exchange_state(self.exchange, w)

    def with_H(self, H: int) -> "CoCoATrainer":
        """A fresh trainer on the same problem with the H knob moved —
        the one sanctioned way to perturb a config (``dataclasses.replace``
        survives the dataclass gaining derived/non-init fields, a
        ``**cfg.__dict__`` splat does not)."""
        return type(self)(dataclasses.replace(self.cfg, H=int(H)),
                          self.A_np, self.b_np)

    def comm_bytes_per_round(self, t: int | None = None) -> int:
        """Modelled bytes through the master per round under the
        configured scheme — sized to the tensors the sharded collectives
        actually move (int8 Delta v + f32 scale for ``compressed``, f32
        otherwise; the alpha round-trip counts the padded blocks).
        ``t`` asks for a specific 1-based round under the elastic
        membership schedule: dropped workers ship nothing, so traffic
        scales with the live-worker count (``None`` = all K live, the
        schedule-free steady state)."""
        K_live = (None if t is None
                  else self.exchange.membership.live_count(t, self.cfg.K))
        return self.scheme.bytes_per_round(
            self.m, self.cfg.K,
            local_state_len=self.cfg.K * self.part.n_padded,
            K_live=K_live, backend=self.exchange.backend)

    def _start(self, p_star: float | None):
        """The history a call records into and its round-key chain."""
        hist = History(p_star=self.p_star if p_star is None else p_star,
                       p_zero=self.p_zero)
        return hist, jax.random.key(self.cfg.seed)

    def _keep_final(self, alpha, w):
        """Copy the final iterate to the host, without the codec-state
        slot a stateful (ef:) codec carried."""
        alpha = dist.unwrap_local_state(self.exchange, alpha)
        self.w_final = np.asarray(w)
        self.alpha_final = part_mod.unpack_alpha(np.asarray(alpha),
                                                 self.part, self.n)

    # ------------------------------------------------------------------
    # virtual-worker (vmap) driver
    # ------------------------------------------------------------------
    def run(self, rounds: int, record_every: int = 1,
            target_eps: float | None = None,
            p_star: float | None = None) -> History:
        """``p_star``, when the caller already knows the optimum, skips
        the host solve behind :attr:`p_star`."""
        with jax.profiler.TraceAnnotation("repro.setup"):
            alpha, w = self.init_state()
            hist, key = self._start(p_star)
        return record_loop(self._round_fn, alpha, w, hist, key, rounds,
                           record_every, target_eps, self._keep_final)

    # ------------------------------------------------------------------
    # shard_map driver (real distribution over devices)
    # ------------------------------------------------------------------
    def build_sharded_round(self, mesh: Mesh):
        """Distributed round via the generic shard_map driver; K must
        equal the mesh axis size. Returns jitted
        ``round_fn(alpha_st, w, key, t)``."""
        assert mesh.devices.size == self.cfg.K, (mesh.devices.size, self.cfg.K)
        return dist.build_sharded_round(self._algo, self.exchange,
                                        dist.place_data(mesh, self._data),
                                        mesh)

    def run_sharded(self, rounds: int, mesh: Mesh | None = None,
                    record_every: int = 1,
                    target_eps: float | None = None,
                    p_star: float | None = None) -> History:
        with jax.profiler.TraceAnnotation("repro.setup"):
            if mesh is None:
                mesh = compat.make_mesh((self.cfg.K,), ("workers",))
            round_fn = self.build_sharded_round(mesh)
            alpha, w = dist.place_state(mesh, *self.init_state())
            hist, key = self._start(p_star)
        return record_loop(round_fn, alpha, w, hist, key, rounds,
                           record_every, target_eps, self._keep_final)

    # ------------------------------------------------------------------
    def objective_of(self, alpha_global: np.ndarray) -> float:
        return float(primal_objective(self.problem, jnp.asarray(self.A_np),
                                      self.b, jnp.asarray(alpha_global)))
