"""The exchange-cell matrix and per-cell compile context.

ONE place defines which (algorithm x exchange spec) cells exist — the
36-cell transport x codec x mode matrix plus the regime and backend
cells — consumed by both ``benchmarks/bench_drivers.py`` (convergence +
byte gates) and the ``python -m repro.analysis`` linter (rule sweep).
Growing the matrix here grows both.

:func:`compile_cell` builds the cell's trainer on the smoke-scale
problem, compiles the sharded round AOT, lifts the optimized HLO into a
:class:`repro.analysis.graph.CollectiveGraph`, and returns a
:class:`CellContext` — everything a lint rule needs.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace

from repro.analysis.graph import CollectiveGraph, lift_hlo
from repro.analysis.traffic import CODEC_WIRE_DTYPE  # noqa: F401 (re-export)
from repro.core.distributed import EXCHANGE_MODES, ExchangeConfig

# every transport x codec cell: the exact transports compose only with
# the f32 identity (validated by CommScheme), `compressed` with all
# three codecs — bare "compressed" (the :int8 alias) is covered by the
# codec-regression test in tests/test_distributed.py
SCHEMES = ("persistent", "spark_faithful", "compressed:f32",
           "compressed:int8", "compressed:int4", "reduce_scatter")
MODES = EXCHANGE_MODES
ALGORITHMS = ("cocoa", "minibatch_scd", "minibatch_sgd")

# Regime cells (full ExchangeConfig specs) on top of the matrix:
# straggler jitter (time-only by assertion), bounded staleness k=2, and
# elastic membership (drop:w@d-r — live-round traffic shrinks with the
# live count while the compiled HLO is membership-invariant).
REGIME_CELLS = (
    ("cocoa", "persistent/straggler:mix(p=0.25,slow=8)"),
    ("cocoa", "persistent/stale:k=2"),
    ("cocoa", "persistent/drop:1@2-4"),
    ("minibatch_sgd", "compressed:int8/drop:1@2-4"),
)

# Codec cells beyond the matrix: the int2/topk base codecs and the
# stateful ef: wrapper (which widens the drivers' local slot with the
# per-worker residual) on every algorithm, plus ef: composed with the
# staleness and elastic-membership regimes and the ring backend — the
# compositions whose codec-state threading is easiest to get wrong.
# topk keeps r=0.125 at smoke scale: k = ceil(0.125*96) = 12 of the
# m = 96 entries, a ratio that actually converges on a 96-vector where
# the 1% default would keep a single coordinate.
CODEC_CELLS = (
    ("cocoa", "compressed:int2"),
    ("cocoa", "compressed:topk(r=0.125)"),
    ("cocoa", "compressed:ef:int4"),
    ("cocoa", "compressed:ef:int2"),
    ("cocoa", "compressed:ef:topk(r=0.125)"),
    ("minibatch_scd", "compressed:ef:int4"),
    ("minibatch_sgd", "compressed:ef:int4"),
    ("cocoa", "compressed:ef:int4/stale:k=2"),
    ("cocoa", "compressed:ef:int4/drop:1@2-4"),
    ("cocoa", "compressed:ef:int4/ring"),
)

# Collective-backend cells: every transport on the explicit ppermute
# ring, plus a stale ring (ring bytes are mode-independent like every
# other transport's).
BACKEND_CELLS = (
    ("cocoa", "persistent/ring"),
    ("cocoa", "compressed:int4/ring"),
    ("minibatch_scd", "reduce_scatter/ring"),
    ("minibatch_sgd", "spark_faithful/ring"),
    ("cocoa", "persistent/ring/stale:k=2"),
)

# The smoke-scale problem every analysis cell compiles against —
# mirrors benchmarks/common.py's smoke tier (m=96, n=256, K=4).
PROBLEM = {"m": 96, "n": 256, "K": 4, "density": 0.2, "zipf_a": 1.1,
           "lam": 1.0, "sgd_step": 0.1, "data_seed": 42,
           "trainer_seed": 0}


@dataclass(frozen=True)
class Cell:
    """One analyzable (algorithm, full exchange spec) point."""
    algorithm: str
    spec: str

    @property
    def id(self) -> str:
        return f"{self.algorithm}={self.spec}"


def matrix_cells() -> tuple[Cell, ...]:
    """The 36-cell algorithm x (transport x codec) x mode matrix."""
    out = []
    for algo in ALGORITHMS:
        for scheme in SCHEMES:
            for mode in MODES:
                spec = scheme if mode == "sync" else f"{scheme}/{mode}"
                out.append(Cell(algo, spec))
    return tuple(out)


def regime_cells() -> tuple[Cell, ...]:
    return tuple(Cell(a, s) for a, s in REGIME_CELLS)


def backend_cells() -> tuple[Cell, ...]:
    return tuple(Cell(a, s) for a, s in BACKEND_CELLS)


def codec_cells() -> tuple[Cell, ...]:
    return tuple(Cell(a, s) for a, s in CODEC_CELLS)


def all_cells() -> tuple[Cell, ...]:
    return (matrix_cells() + regime_cells() + backend_cells()
            + codec_cells())


def resolve_cells(selector: str) -> tuple[Cell, ...]:
    """CLI cell selector: ``all`` | ``matrix`` | ``regime`` | ``backend``
    or ``codec``, or a comma-separated list of ``algo=spec`` entries."""
    named = {"all": all_cells, "matrix": matrix_cells,
             "regime": regime_cells, "backend": backend_cells,
             "codec": codec_cells}
    if selector in named:
        return named[selector]()
    out = []
    for entry in selector.split(","):
        algo, _, spec = entry.partition("=")
        if not spec or algo not in ALGORITHMS:
            raise ValueError(
                f"bad cell {entry!r}: expected algo=spec with algo in "
                f"{ALGORITHMS} (or one of {sorted(named)})")
        ExchangeConfig.parse(spec)  # validate early
        out.append(Cell(algo, spec))
    return tuple(out)


_PROBLEM_CACHE: dict = {}


def problem():
    """(A, b) for the smoke-scale analysis problem (cached)."""
    from repro.data import make_glm_data
    key = "smoke"
    if key not in _PROBLEM_CACHE:
        p = PROBLEM
        A, b, _ = make_glm_data(m=p["m"], n=p["n"], density=p["density"],
                                zipf_a=p["zipf_a"], seed=p["data_seed"])
        _PROBLEM_CACHE[key] = (A, b)
    return _PROBLEM_CACHE[key]


def build_trainer(cell: Cell, K: int | None = None):
    """The cell's trainer on the smoke problem (same construction as
    bench_drivers' `_make_trainer`, minus the tier plumbing)."""
    from repro.core import (CoCoAConfig, CoCoATrainer, MinibatchSCD,
                            MinibatchSGD, SGDConfig)
    p = PROBLEM
    K = K or p["K"]
    A, b = problem()
    if cell.algorithm == "minibatch_sgd":
        return MinibatchSGD(
            SGDConfig(batch_frac=1.0, step_size=p["sgd_step"], lam=p["lam"],
                      K=K, seed=p["trainer_seed"], exchange=cell.spec), A, b)
    n_local = -(p["n"] // -K)
    cfg = CoCoAConfig(K=K, H=n_local, lam=p["lam"], solver="scd_ref",
                      exchange=cell.spec, seed=p["trainer_seed"])
    cls = MinibatchSCD if cell.algorithm == "minibatch_scd" \
        else CoCoATrainer
    return cls(cfg, A, b)


@dataclass
class CellContext:
    """Everything a cell-scoped lint rule gets to look at."""
    cell: Cell
    trainer: object
    round_fn: object
    hlo_text: str
    graph: CollectiveGraph
    K: int
    exchange: object            # resolved ExchangeConfig
    update_len: int             # the exchanged update-vector length
    mesh: object = None
    extra: dict = field(default_factory=dict)

    @property
    def id(self) -> str:
        return self.cell.id

    def compile_variant(self, spec: str) -> "CellContext":
        """Compile a sibling cell (same algorithm/mesh, different spec) —
        used by membership-invariant to compare against full membership."""
        return compile_cell(replace(self.cell, spec=spec), mesh=self.mesh)


def lower_round_hlo(trainer, round_fn) -> str:
    """Optimized HLO text of the sharded round (AOT — does not populate
    the jit call cache, so single-compile still sees a cold function)."""
    import jax
    local, shared = trainer.init_state()
    return round_fn.lower(local, shared, jax.random.key(0),
                          1).compile().as_text()


def compile_cell(cell: Cell, mesh=None, K: int | None = None
                 ) -> CellContext:
    """Build + AOT-compile one cell and lift its collective graph."""
    import jax

    from repro.utils.compat import make_mesh

    if mesh is None:
        K = K or min(PROBLEM["K"], len(jax.devices()))
        mesh = make_mesh((K,), ("workers",))
    K = mesh.devices.size
    tr = build_trainer(cell, K=K)
    round_fn = tr.build_sharded_round(mesh)
    hlo = lower_round_hlo(tr, round_fn)
    # the exchanged update vector: SGD averages the n-length gradient,
    # the CoCoA family exchanges the m-length shared vector
    update_len = tr.n if cell.algorithm == "minibatch_sgd" else tr.m
    return CellContext(cell=cell, trainer=tr, round_fn=round_fn,
                       hlo_text=hlo, graph=lift_hlo(hlo), K=K,
                       exchange=tr.exchange, update_len=update_len,
                       mesh=mesh)
