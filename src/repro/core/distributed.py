"""Unified distributed-driver layer: one communication substrate for all
three algorithms (paper §5.3/§5.4).

The paper's headline result comes from applying the *same* framework and
algorithmic optimizations to three distributed linear ML algorithms —
CoCoA, mini-batch SCD, and mini-batch SGD. That comparison is only
meaningful when every algorithm runs under the same communication
substrate, so this module factors it out:

  * :class:`CommScheme` — a *transport* (which collective moves the
    update) composed with an *update codec* (what one worker's update
    looks like on the wire, ``repro.comm``). Transports:

      - ``persistent``      per-worker state lives on its worker across
        rounds (the paper's "persistent local memory" / (B)*, (D)*
        optimization); the aggregate travels via an in-place ``psum``.
      - ``spark_faithful``  everything is shipped through the master
        every round: updates are collected (all-gather) and summed
        locally instead of psum'd, and per-worker persistent state is
        all-gathered and re-sliced — mathematically the identity, but
        the extra collective traffic is real and visible in the HLO.
      - ``compressed``      beyond-paper: each worker's update is
        codec-encoded before the all-gather and decoded + summed
        locally. The codec is named after a colon — ``compressed:int8``
        (absmax int8 + f32 scale, 4x less traffic than f32),
        ``compressed:int4`` (two elements per byte, ~8x),
        ``compressed:int2`` (four per byte, ~16x),
        ``compressed:topk(r=..)`` (ship only the ceil(r*L) largest
        entries), or ``compressed:f32`` (the identity codec — the bare
        transport). Bare ``"compressed"`` aliases ``compressed:int8``,
        so every pre-codec config keeps its exact behavior. The
        ``ef:``-prefixed codecs (``compressed:ef:int4`` ...) add error
        feedback: the encode carries a per-worker residual between
        rounds, which widens the drivers' ``local`` slot to the
        ``(local, codec_state)`` pair (build it with
        :func:`wrap_local_state`) the same way ``stale`` widens
        ``shared``.
      - ``reduce_scatter``  beyond-paper: the update exchange as an
        explicit ``psum_scatter`` + ``all_gather`` pair (the classic
        ring decomposition of all-reduce) — each worker moves only
        2·(K-1)/K of the update vector each way instead of the full
        vector, the cheapest exact f32 exchange on a ring.

    Both execution drivers call the ONE codec object (so they cannot
    drift) and the byte accounting is sized to what the collectives
    actually move (``codec.wire_bytes`` per worker each way — int8/int4
    payloads + the 4-byte scale under ``compressed``, f32 otherwise).

    The collective *mechanics* under the transports live in
    ``repro.comm.collectives`` behind the pluggable
    :class:`~repro.comm.collectives.CollectiveBackend` axis (``xla``
    fused collectives vs an explicit ``ring`` of ``ppermute`` hops, the
    Alchemist-style fabric swap); :class:`ExchangeConfig` carries the
    backend name as its own spec segment (default ``xla``).

  * :class:`ExchangeMode` — the *staleness* axis, orthogonal to the
    scheme (paper §4-§5: Spark's scheduling delay makes workers compute
    against stale state; treating that delay as an algorithmic knob is
    the other half of the computation/communication trade-off):

      - ``sync``   bulk-synchronous: the round-``t`` aggregate is
        applied before round ``t+1`` computes (every scheme above, as
        in the paper's optimized implementations).
      - ``stale``  ``k``-round-bounded-delay apply (``stale`` is k=1,
        ``stale:k=2`` two rounds deep, ...): workers compute round
        ``t`` against shared state that has only absorbed aggregates
        through round ``t-1-k``; the last ``k`` aggregates travel as
        an explicit stacked *pending queue*, the oldest applied while
        round ``t`` computes. The collective still runs every round
        (same wire bytes, same HLO traffic), but nothing waits on it —
        the exchange can hide behind up to ``k`` rounds of compute,
        which is exactly the overlap the trade-off layer's
        ``TimeModel`` charges for.

  * :class:`StragglerProfile` — per-worker compute-jitter injection
    (the paper's straggling-executor regime, §4). Time-only by
    construction: under a bulk-synchronous barrier every round waits
    for its slowest worker, so the drivers ignore the profile
    numerically (trajectories and wire traffic are straggler-
    invariant — regression-tested) while ``TimeModel`` stretches
    compute by the expected barrier factor ``E[max over K workers]``.

  * :class:`MembershipSchedule` — elastic worker membership
    (``drop:1@5-9``): liveness is evaluated *in-graph* from the round
    index, so ONE compiled round serves every round. A dropped worker
    contributes an exact-zero update (zeroed before codec encode —
    zero is a fixed point of every codec), keeps its persistent local
    state frozen, and mean-style aggregates are reweighted by the
    live-worker count; the HLO collectives are membership-invariant,
    only the byte model's ``K_live`` changes.

  * :class:`ExchangeConfig` — all four of the above in one frozen
    value, round-tripping to/from a ``/``-separated spec string
    (``"compressed:int4/stale:k=2/straggler:mix(p=0.1,slow=8)/
    drop:1@5-9"``). This is the ONE surface configs, driver builders,
    ``TimeModel`` and ``sweep_H`` accept; the scattered
    ``comm_scheme=`` / ``exchange_mode=`` knobs are deprecated aliases
    that fold into it via :func:`resolve_exchange`.

  * generic round drivers over the ``workers`` mesh axis — a *virtual*
    driver (vmap/lax.map over stacked ``(K, ...)`` worker arrays on
    however many real devices exist) and a *sharded* driver (real
    distribution via ``shard_map`` with explicit collectives). An
    algorithm plugs in via the :class:`RoundAlgorithm` protocol; the
    same object drives both paths, so the math can only differ in
    communication mechanics.

Per-worker RNG is derived identically in both drivers (``split`` of the
round key into K worker keys) and is untouched by the exchange mode, so
a virtual and a sharded run with the same seed follow the same
trajectory up to reduction-order float jitter — in either mode, under
any membership schedule.

Under ``stale`` the drivers' ``shared`` slot widens to the pair
``(shared, queue)`` — a stacked ``(k, ...)`` pending leaf per shared
leaf (build it with :func:`init_exchange_state`); a finished run
flushes every still-pending aggregate with ``round_fn.flush`` so a
short stale run produces the same iterate as a sync run (the delayed
apply is a pipeline shift, not a lost update — pinned against a serial
replay in the tests).
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass, field
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import UpdateCodec, get_codec
from repro.comm.collectives import (FP_ITEMSIZE, COLLECTIVE_BACKENDS,
                                    get_backend, exchange_all_reduce,
                                    exchange_roundtrip_state)
from repro.utils import compat
from repro.utils.deprecation import warn_deprecated

# the transports; ``compressed`` composes with a codec suffix — the
# canonical sweep set keeps the bare aliases (compressed == :int8)
COMM_TRANSPORTS = ("persistent", "spark_faithful", "compressed",
                   "reduce_scatter")
COMM_SCHEMES = COMM_TRANSPORTS
EXCHANGE_MODES = ("sync", "stale")
STRAGGLER_KINDS = ("none", "det", "lognormal", "mix")

# the one-line grammar every exchange-spec parse error points at
EXCHANGE_GRAMMAR = ("<transport>[:<codec>] | "
                    + " | ".join(COLLECTIVE_BACKENDS)
                    + " | sync | stale[:k=<int>] | "
                    "straggler:<kind>[(p=..,slow=..,sigma=..)] | "
                    "drop:<worker>@<round>[-<round>]")


# ---------------------------------------------------------------------------
# back-compat shims for the pre-codec quantizer API — the single int8
# source of truth now lives in repro.comm.codec; both drivers reach it
# through the scheme's codec object
# ---------------------------------------------------------------------------
def quantize_update(dv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Absmax int8 quantization of one worker's update vector
    (``Int8Codec.encode``: the jnp oracle off TPU, the fused Pallas
    quantize+pack kernel on TPU).

    Returns ``(q, scale)`` with ``q`` int8 in [-127, 127] and ``scale``
    a scalar f32 such that ``dequantize_update(q, scale) ~= dv``.
    """
    return get_codec("int8").encode(dv)


def dequantize_update(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# communication schemes
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CommScheme:
    """One of the paper's communication schemes (§5.3) as transport x
    codec — ``name`` is ``"<transport>"`` or ``"compressed:<codec>"``
    (bare ``"compressed"`` aliases ``compressed:int8``). Carries both
    the collective mechanics (used inside the round drivers) and the
    byte accounting for the overhead model, so modelled traffic cannot
    drift from what is actually moved.
    """
    name: str

    @classmethod
    def parse(cls, spec: "CommScheme | str") -> "CommScheme":
        """The canonical (non-deprecated) scheme lookup: a pass-through
        for :class:`CommScheme` instances, validated construction for
        ``"<transport>[:<codec>]"`` strings."""
        return spec if isinstance(spec, CommScheme) else cls(str(spec))

    def __post_init__(self):
        transport, _, codec = self.name.partition(":")
        if transport not in COMM_TRANSPORTS:
            raise ValueError(f"unknown comm scheme {self.name!r}; "
                             f"known transports: {COMM_TRANSPORTS} "
                             f"(codecs compose as 'compressed:<codec>')")
        if codec:
            if transport != "compressed":
                raise ValueError(
                    f"comm scheme {self.name!r}: only the 'compressed' "
                    f"transport takes a codec suffix ('{transport}' "
                    f"moves exact f32 by construction)")
            get_codec(codec)  # raises on unknown codec names

    @property
    def transport(self) -> str:
        return self.name.partition(":")[0]

    @property
    def codec(self) -> UpdateCodec:
        """The wire codec this scheme's exchange runs through: the named
        one for ``compressed`` (int8 when bare — the pre-codec default),
        the f32 identity for every exact-f32 transport."""
        transport, _, codec = self.name.partition(":")
        if transport == "compressed":
            return get_codec(codec or "int8")
        return get_codec("f32")

    @property
    def persistent_local_state(self) -> bool:
        """May per-worker state (e.g. alpha_[k]) stay device-resident?"""
        return self.transport != "spark_faithful"

    # -- aggregation inside shard_map (per-shard view) ---------------------
    def all_reduce(self, update: jax.Array, axis: str,
                   backend=None, state=None):
        """Sum the per-worker 1-D update across the mesh axis, moved by
        ``backend``'s collectives (name, backend object, or ``None`` for
        the fused ``xla`` fabric — ``repro.comm.collectives``).
        ``state`` is this worker's codec-state carry (the error-feedback
        residual): when given, the return value is ``(total,
        new_state)`` instead of the bare aggregate."""
        return exchange_all_reduce(self.transport, self.codec, update,
                                   axis, backend, state=state)

    # -- aggregation over stacked (K, L) updates (virtual driver) ----------
    def all_reduce_stacked(self, updates: jax.Array, state=None):
        """``state`` is the stacked ``(K, ...)`` per-worker codec-state
        carry; when given the encode runs through the codec's stateful
        entry point and the call returns ``(total, new_state)``."""
        if self.transport == "compressed":
            if state is None:
                parts = jax.vmap(self.codec.encode)(updates)
            else:
                parts, state = jax.vmap(
                    self.codec.encode_with_state)(updates, state)
            # fused decode+reduce, same method the sharded exchange
            # calls — the virtual/sharded bit-identity contract rides
            # on both drivers emitting the identical decode+sum HLO
            total = self.codec.decode_stacked_sum(parts,
                                                  updates.shape[1])
        else:
            total = jnp.sum(updates, axis=0)
        return total if state is None else (total, state)

    # -- persistent-state round trip (sharded driver only) -----------------
    def roundtrip_local_state(self, state: jax.Array, axis: str,
                              backend=None) -> jax.Array:
        """``spark_faithful`` ships per-worker persistent state through
        the master every round: all-gather, then each worker re-slices
        its own block — the identity, with real collective traffic."""
        if self.persistent_local_state or state.size == 0:
            return state
        return exchange_roundtrip_state(state, axis, backend)

    # -- modelled traffic --------------------------------------------------
    def bytes_per_round(self, update_len: int, K: int,
                        local_state_len: int = 0,
                        K_live: int | None = None,
                        backend=None) -> int:
        """Bytes on the wire per round (paper Fig 1 + §5.3), sized to
        the dtypes the collectives actually move — the backend owns the
        formula (:meth:`~repro.comm.collectives.CollectiveBackend.
        wire_bytes`), since the same transport moves different volumes
        on a fused collective vs an explicit ring.  ``K_live`` (elastic
        membership) is the number of live workers this round; ``None``
        (the default) means all K live, reproducing the pre-elastic
        formula bit for bit."""
        return get_backend(backend).wire_bytes(
            self.transport, self.codec, update_len, K,
            local_state_len=local_state_len, K_live=K_live)


def get_scheme(name: str) -> CommScheme:
    """Deprecated scheme lookup — use :meth:`CommScheme.parse` (or fold
    the scheme into a unified :class:`ExchangeConfig` spec)."""
    warn_deprecated(
        "get_scheme() is deprecated; use CommScheme.parse(spec) or the "
        "unified ExchangeConfig.parse(spec)")
    return CommScheme.parse(name)


# ---------------------------------------------------------------------------
# exchange modes (the staleness axis, orthogonal to the comm scheme)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ExchangeMode:
    """``sync`` (bulk-synchronous apply) or ``stale`` (``k``-round-
    bounded-delay apply: the aggregate computed in round ``t`` is
    applied during round ``t+k`` while workers compute against state
    that has only absorbed aggregates through round ``t-1-k`` — the
    paper's Spark scheduling-delay regime as an explicit knob, now with
    the delay depth as a parameter). The canonical string spelling is
    ``"sync"``, ``"stale"`` (k=1), or ``"stale:k=<int>"``."""
    name: str
    k: int = 1

    @classmethod
    def parse(cls, spec: "ExchangeMode | str") -> "ExchangeMode":
        """The canonical (non-deprecated) mode lookup: a pass-through
        for :class:`ExchangeMode` instances, validated construction for
        ``"sync"`` / ``"stale"`` / ``"stale:k=<int>"`` strings."""
        if isinstance(spec, ExchangeMode):
            return spec
        name, _, opts = str(spec).partition(":")
        if name not in EXCHANGE_MODES:
            raise ValueError(f"unknown exchange mode {spec!r}; "
                             f"known: {EXCHANGE_MODES} (bounded "
                             f"staleness spells 'stale:k=<int>')")
        if not opts:
            return cls(name)
        m = re.fullmatch(r"k=([0-9]+)", opts)
        if name != "stale" or not m:
            raise ValueError(f"unknown exchange mode {spec!r}; the only "
                             f"parameterized spelling is 'stale:k=<int>' "
                             f"(e.g. 'stale:k=2')")
        return cls(name, int(m.group(1)))

    def __post_init__(self):
        if self.name not in EXCHANGE_MODES:
            raise ValueError(f"unknown exchange mode {self.name!r}; "
                             f"known: {EXCHANGE_MODES}")
        if self.k < 1:
            raise ValueError(f"exchange mode {self.name!r}: the staleness "
                             f"bound k must be >= 1, got {self.k}")
        if self.name == "sync" and self.k != 1:
            raise ValueError(f"exchange mode 'sync' takes no staleness "
                             f"bound (got k={self.k}); spell a bounded "
                             f"delay as 'stale:k={self.k}'")

    @property
    def stale(self) -> bool:
        return self.name == "stale"

    @property
    def spec(self) -> str:
        """Canonical string spelling (``parse(spec)`` round-trips)."""
        return self.name if self.k == 1 else f"{self.name}:k={self.k}"


def get_mode(mode: "ExchangeMode | str") -> ExchangeMode:
    """Deprecated mode lookup — use :meth:`ExchangeMode.parse` (or fold
    the mode into a unified :class:`ExchangeConfig` spec)."""
    warn_deprecated(
        "get_mode() is deprecated; use ExchangeMode.parse(spec) or the "
        "unified ExchangeConfig.parse(spec)")
    return ExchangeMode.parse(mode)


# ---------------------------------------------------------------------------
# straggler profiles (the fault/jitter injection layer)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _lognormal_barrier_mult(sigma: float, K: int,
                            samples: int = 8192) -> float:
    """E[max over K workers] of a mean-1 lognormal multiplier, by
    fixed-seed Monte Carlo (no closed form). Deterministic, cached."""
    z = np.random.default_rng(20260808).standard_normal((samples, K))
    mult = np.exp(sigma * z - 0.5 * sigma * sigma)
    return float(np.mean(np.max(mult, axis=1)))


@dataclass(frozen=True)
class StragglerProfile:
    """Per-worker compute-time multiplier distribution — the paper's
    straggling-executor regime (§4, Figs 4-5) as an explicit knob.

    Under a bulk-synchronous barrier every round waits for its slowest
    worker, so straggling changes *wall-clock only*: the drivers ignore
    the profile numerically (trajectories and wire traffic are
    straggler-invariant — regression-tested) while the trade-off
    layer's ``TimeModel`` charges compute as the max over workers.

      * ``none``              every worker runs at 1x.
      * ``det(slow=S)``       worker 0 is deterministically S× slower —
        the paper's "one bad executor" case; barrier factor exactly S.
      * ``lognormal(sigma=σ)``  mean-1 lognormal jitter on every worker
        (``exp(σz - σ²/2)``); barrier factor E[max of K] by fixed-seed
        Monte Carlo.
      * ``mix(p=P,slow=S)``   heavy-tail mix: each worker independently
        S× slow with probability P; barrier factor
        ``1 + (S-1)·(1-(1-P)^K)`` in closed form.

    ``multipliers`` samples one round's per-worker multipliers keyed
    off the same round-key ``split`` the drivers use for worker RNG.
    Canonical string spelling: ``"straggler:mix(p=0.1,slow=8)"`` etc.
    """
    kind: str = "none"
    slow: float = 4.0
    p: float = 0.1
    sigma: float = 0.5

    _PARAMS = {"none": (), "det": ("slow",), "lognormal": ("sigma",),
               "mix": ("p", "slow")}

    @classmethod
    def parse(cls, spec: "StragglerProfile | str") -> "StragglerProfile":
        if isinstance(spec, StragglerProfile):
            return spec
        body = str(spec)
        body = body[len("straggler:"):] if body.startswith("straggler:") \
            else body
        m = re.fullmatch(r"([a-z_]+)(?:\(([^()]*)\))?", body)
        if not m or m.group(1) not in STRAGGLER_KINDS:
            raise ValueError(f"unknown straggler profile {spec!r}; known "
                             f"kinds: {STRAGGLER_KINDS}, parameterized as "
                             f"'straggler:mix(p=0.1,slow=8)'")
        kind, params = m.group(1), m.group(2)
        allowed = cls._PARAMS[kind]
        kwargs = {}
        for item in (params.split(",") if params else ()):
            key, sep, val = item.partition("=")
            key = key.strip()
            if not sep or key not in allowed:
                raise ValueError(
                    f"straggler profile {spec!r}: '{kind}' takes "
                    f"{allowed or 'no'} parameters, got {item!r}")
            try:
                kwargs[key] = float(val)
            except ValueError:
                raise ValueError(f"straggler profile {spec!r}: parameter "
                                 f"{key}={val!r} is not a number") from None
        return cls(kind, **kwargs)

    def __post_init__(self):
        if self.kind not in STRAGGLER_KINDS:
            raise ValueError(f"unknown straggler profile kind "
                             f"{self.kind!r}; known: {STRAGGLER_KINDS}")
        if self.slow < 1.0:
            raise ValueError(f"straggler slow multiplier must be >= 1, "
                             f"got {self.slow}")
        if not 0.0 <= self.p <= 1.0:
            raise ValueError(f"straggler probability p must be in [0, 1], "
                             f"got {self.p}")
        if self.sigma < 0.0:
            raise ValueError(f"straggler lognormal sigma must be >= 0, "
                             f"got {self.sigma}")

    @property
    def active(self) -> bool:
        return self.kind != "none"

    @property
    def spec(self) -> str:
        """Canonical string spelling (``parse(spec)`` round-trips; only
        the kind's own parameters are printed)."""
        fmt = {"slow": self.slow, "p": self.p, "sigma": self.sigma}
        args = ",".join(f"{k}={fmt[k]:g}" for k in self._PARAMS[self.kind])
        return f"straggler:{self.kind}" + (f"({args})" if args else "")

    def multipliers(self, key: jax.Array, K: int) -> jax.Array:
        """One round's per-worker compute-time multipliers, shape
        ``(K,)`` f32 — derived from the round key with the same
        ``split``-into-K-worker-keys plumbing the drivers use, so the
        jitter stream is reproducible and independent per worker."""
        if self.kind == "none":
            return jnp.ones((K,), jnp.float32)
        if self.kind == "det":
            return jnp.where(jnp.arange(K) == 0, self.slow,
                             1.0).astype(jnp.float32)
        keys = jax.random.split(jax.random.fold_in(key, 0x57A6), K)
        if self.kind == "lognormal":
            z = jax.vmap(lambda kk: jax.random.normal(kk, ()))(keys)
            return jnp.exp(self.sigma * z
                           - 0.5 * self.sigma**2).astype(jnp.float32)
        hit = jax.vmap(lambda kk: jax.random.bernoulli(kk, self.p))(keys)
        return jnp.where(hit, self.slow, 1.0).astype(jnp.float32)

    def barrier_mults(self, key: jax.Array, K: int,
                      rounds: int) -> jax.Array:
        """``(rounds,)`` sampled per-round barrier factors — the max
        over workers of :meth:`multipliers`, one round per key."""
        keys = jax.random.split(key, rounds)
        return jax.vmap(lambda kk: jnp.max(self.multipliers(kk, K)))(keys)

    def expected_barrier_mult(self, K: int) -> float:
        """E[max over K workers] of the multiplier — the factor a
        bulk-synchronous barrier stretches compute by (what
        ``TimeModel`` charges)."""
        if K < 1:
            raise ValueError(f"straggler barrier factor needs the worker "
                             f"count K >= 1, got {K}")
        if self.kind == "none":
            return 1.0
        if self.kind == "det":
            return float(self.slow)
        if self.kind == "mix":
            return 1.0 + (self.slow - 1.0) * (1.0 - (1.0 - self.p) ** K)
        return _lognormal_barrier_mult(self.sigma, K)


# ---------------------------------------------------------------------------
# elastic membership schedules
# ---------------------------------------------------------------------------
_DROP_RE = re.compile(r"drop:([0-9]+)@([0-9]+)(?:-([0-9]+))?")


@dataclass(frozen=True)
class MembershipSchedule:
    """Elastic worker membership: each event removes one worker for an
    inclusive window of 1-based rounds (``(worker, first, last)``;
    ``last=None`` means it never rejoins). Spelled ``"drop:1@5"`` /
    ``"drop:1@5-9"`` in exchange specs; multiple ``drop`` segments
    compose.

    Membership is evaluated *in-graph* from the traced round index, so
    one compiled round serves every round: a dropped worker still
    participates in the collectives but contributes an exact-zero
    update (zeroed BEFORE codec encode — zero is a guaranteed fixed
    point of every codec) and its persistent local state is frozen.
    The wire traffic therefore changes only via the live-worker count
    in the byte model, never via the HLO.
    """
    events: tuple = ()

    @staticmethod
    def parse_event(seg: str) -> tuple:
        m = _DROP_RE.fullmatch(seg)
        if not m:
            raise ValueError(f"malformed membership segment {seg!r}; the "
                             f"grammar is 'drop:<worker>@<round>' or "
                             f"'drop:<worker>@<first>-<last>'")
        w, d, r = int(m.group(1)), int(m.group(2)), m.group(3)
        return (w, d, None if r is None else int(r))

    @classmethod
    def parse(cls, spec: "MembershipSchedule | str") -> "MembershipSchedule":
        if isinstance(spec, MembershipSchedule):
            return spec
        segs = [s for s in str(spec).split("/") if s]
        return cls(tuple(cls.parse_event(s) for s in segs))

    def __post_init__(self):
        norm = []
        for ev in self.events:
            w, d, r = ev
            if w < 0 or d < 1 or (r is not None and r < d):
                raise ValueError(
                    f"membership event {ev!r}: need worker >= 0, first "
                    f"round >= 1 (rounds are 1-based) and last >= first")
            norm.append((int(w), int(d), None if r is None else int(r)))
        object.__setattr__(self, "events", tuple(norm))

    @property
    def empty(self) -> bool:
        return not self.events

    @property
    def spec(self) -> str:
        return "/".join(f"drop:{w}@{d}" if r is None else f"drop:{w}@{d}-{r}"
                        for (w, d, r) in self.events)

    def check_workers(self, K: int) -> None:
        for (w, _, _) in self.events:
            if w >= K:
                raise ValueError(f"membership schedule {self.spec!r} drops "
                                 f"worker {w} but the run has only K={K} "
                                 f"workers")

    def live_mask(self, t, K: int) -> jax.Array:
        """``(K,)`` f32 {0,1} mask of live workers at 1-based round
        ``t`` (``t`` may be traced — elementwise ops only, no
        collectives, so one compile serves every round)."""
        self.check_workers(K)
        mask = jnp.ones((K,), jnp.float32)
        for (w, d, r) in self.events:
            absent = (t >= d) if r is None else ((t >= d) & (t <= r))
            mask = mask.at[w].multiply(jnp.where(absent, 0.0, 1.0))
        return mask

    def live_count(self, t: int, K: int) -> int:
        """Concrete live-worker count at a concrete round ``t`` (the
        byte model's ``K_live``)."""
        self.check_workers(K)

        def absent(w):
            return any(w == ew and t >= d and (r is None or t <= r)
                       for (ew, d, r) in self.events)

        return sum(0 if absent(w) else 1 for w in range(K))


# ---------------------------------------------------------------------------
# the unified exchange configuration
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ExchangeConfig:
    """Everything about how one run exchanges updates, in one frozen
    value: the comm scheme (transport x codec), the collective backend
    (which fabric moves the bytes — ``repro.comm.collectives``), the
    exchange mode (sync / bounded staleness), the straggler profile,
    and the elastic membership schedule.

    Round-trips to/from a ``"/"``-separated spec string whose segments
    may appear in any order::

        ExchangeConfig.parse("compressed:int4/stale:k=2")
        ExchangeConfig.parse("compressed:int4/ring/stale:k=2")
        ExchangeConfig.parse("persistent/straggler:mix(p=0.1,slow=8)")
        ExchangeConfig.parse("spark_faithful/drop:1@5-9/drop:3@7")

    Omitted segments take their defaults (``persistent``, ``sync``, no
    stragglers, full membership); ``str(cfg)`` prints the canonical
    spec with default segments elided. This is the ONE surface the
    drivers, the trainer configs, ``TimeModel`` and ``sweep_H`` accept;
    the scattered ``comm_scheme=`` / ``exchange_mode=`` string knobs
    are deprecated aliases that fold into it (one release of warning).
    """
    scheme: CommScheme = field(default_factory=lambda: CommScheme("persistent"))
    mode: ExchangeMode = field(default_factory=lambda: ExchangeMode("sync"))
    straggler: StragglerProfile = field(default_factory=StragglerProfile)
    membership: MembershipSchedule = field(default_factory=MembershipSchedule)
    backend: str = "xla"

    def __post_init__(self):
        # constructor convenience: each component may be given as its
        # own string spelling
        if isinstance(self.scheme, str):
            object.__setattr__(self, "scheme", CommScheme.parse(self.scheme))
        if isinstance(self.mode, str):
            object.__setattr__(self, "mode", ExchangeMode.parse(self.mode))
        if isinstance(self.straggler, str):
            object.__setattr__(self, "straggler",
                               StragglerProfile.parse(self.straggler))
        if isinstance(self.membership, (str, tuple)):
            object.__setattr__(
                self, "membership",
                MembershipSchedule.parse(self.membership)
                if isinstance(self.membership, str)
                else MembershipSchedule(self.membership))
        # the backend is stored by name (a backend object is folded to
        # its name so the config stays a frozen hashable value);
        # get_backend raises on unknown names
        object.__setattr__(self, "backend", get_backend(self.backend).name)

    @classmethod
    def parse(cls, spec: "ExchangeConfig | CommScheme | ExchangeMode | str",
              ) -> "ExchangeConfig":
        """Parse a spec string (or pass through / wrap an already-typed
        value). Segments are classified by their head token, so order
        never matters; duplicate scheme/mode/straggler segments are
        rejected loudly."""
        if isinstance(spec, ExchangeConfig):
            return spec
        if isinstance(spec, CommScheme):
            return cls(scheme=spec)
        if isinstance(spec, ExchangeMode):
            return cls(mode=spec)
        scheme = mode = straggler = backend = None
        events: list = []
        for seg in str(spec).split("/"):
            head = seg.partition(":")[0]
            if head in COLLECTIVE_BACKENDS:
                if seg != head:
                    raise ValueError(
                        f"exchange spec {spec!r}: collective-backend "
                        f"segment {seg!r} takes no parameters")
                if backend is not None:
                    raise ValueError(f"exchange spec {spec!r}: duplicate "
                                     f"collective-backend segment {seg!r}")
                backend = head
            elif head in COMM_TRANSPORTS:
                if scheme is not None:
                    raise ValueError(f"exchange spec {spec!r}: duplicate "
                                     f"comm-scheme segment {seg!r}")
                scheme = CommScheme.parse(seg)
            elif head in EXCHANGE_MODES:
                if mode is not None:
                    raise ValueError(f"exchange spec {spec!r}: duplicate "
                                     f"exchange-mode segment {seg!r}")
                mode = ExchangeMode.parse(seg)
            elif head == "straggler":
                if straggler is not None:
                    raise ValueError(f"exchange spec {spec!r}: duplicate "
                                     f"straggler segment {seg!r}")
                straggler = StragglerProfile.parse(seg)
            elif head == "drop":
                events.append(MembershipSchedule.parse_event(seg))
            else:
                raise ValueError(
                    f"unknown exchange spec segment {seg!r} in {spec!r}; "
                    f"the grammar is {EXCHANGE_GRAMMAR}")
        return cls(scheme=scheme if scheme is not None
                   else CommScheme("persistent"),
                   mode=mode if mode is not None else ExchangeMode("sync"),
                   straggler=straggler if straggler is not None
                   else StragglerProfile(),
                   membership=MembershipSchedule(tuple(events)),
                   backend=backend if backend is not None else "xla")

    @property
    def spec(self) -> str:
        """Canonical spec string: scheme first, then the backend when
        not the default ``xla``, then every other non-default segment;
        ``parse(spec)`` round-trips."""
        segs = [self.scheme.name]
        if self.backend != "xla":
            segs.append(self.backend)
        if self.mode.spec != "sync":
            segs.append(self.mode.spec)
        if self.straggler.active:
            segs.append(self.straggler.spec)
        if not self.membership.empty:
            segs.append(self.membership.spec)
        return "/".join(segs)

    def __str__(self) -> str:
        return self.spec


def resolve_exchange(exchange=None, *, comm_scheme=None, exchange_mode=None,
                     owner: str = "") -> ExchangeConfig:
    """Fold the unified ``exchange`` spec and the deprecated
    ``comm_scheme`` / ``exchange_mode`` knobs into ONE
    :class:`ExchangeConfig`.

    ``exchange`` given: it is authoritative; a legacy knob may ride
    along only if it agrees (configs re-pass their stored canonical
    values through ``dataclasses.replace``), otherwise ValueError.
    ``exchange`` absent: the legacy knobs build the config, with one
    :class:`~repro.utils.deprecation.ReproDeprecationWarning` when a
    non-default legacy value is used.
    """
    where = f"{owner}: " if owner else ""
    sch = None if comm_scheme is None else CommScheme.parse(comm_scheme)
    mod = None if exchange_mode is None else ExchangeMode.parse(exchange_mode)
    if exchange is not None:
        ex = ExchangeConfig.parse(exchange)
        conflicts = []
        if sch is not None and sch != ex.scheme:
            conflicts.append(f"comm_scheme={sch.name!r} vs exchange scheme "
                             f"{ex.scheme.name!r}")
        if mod is not None and mod != ex.mode:
            conflicts.append(f"exchange_mode={mod.spec!r} vs exchange mode "
                             f"{ex.mode.spec!r}")
        if conflicts:
            raise ValueError(
                f"{where}exchange={ex.spec!r} conflicts with deprecated "
                f"knob(s): {'; '.join(conflicts)} — drop the deprecated "
                f"spelling")
        return ex
    legacy = []
    if sch is not None and sch.name != "persistent":
        legacy.append(f"comm_scheme={sch.name!r}")
    if mod is not None and mod.spec != "sync":
        legacy.append(f"exchange_mode={mod.spec!r}")
    if legacy:
        warn_deprecated(
            f"{where}{' and '.join(legacy)} is deprecated; pass the "
            f"unified exchange spec instead (e.g. "
            f"exchange='compressed:int4/stale:k=2')", stacklevel=4)
    return ExchangeConfig(scheme=sch if sch is not None
                          else CommScheme("persistent"),
                          mode=mod if mod is not None
                          else ExchangeMode("sync"))


def init_exchange_state(mode: "ExchangeConfig | ExchangeMode | str", shared,
                        pending=None):
    """The drivers' ``shared`` slot for the given mode (an
    :class:`ExchangeConfig` is accepted and contributes its mode):
    ``sync`` passes the shared state through untouched; ``stale``
    pairs it with the carried pending-aggregate queue — a stacked
    ``(k, ...)`` leaf per shared leaf, zeros until real aggregates have
    flowed in (every algorithm here all-reduces an update shaped like
    its shared state, so stacked ``zeros_like(shared)`` is the default
    template). ``pending``, when given, must already be the stacked
    queue."""
    if isinstance(mode, ExchangeConfig):
        mode = mode.mode
    mode = ExchangeMode.parse(mode)
    if not mode.stale:
        return shared
    if pending is None:
        pending = jax.tree_util.tree_map(
            lambda s: jnp.zeros((mode.k,) + s.shape, s.dtype), shared)
    return (shared, pending)


def wrap_local_state(exchange, local, update_len: int, K: int):
    """The drivers' ``local`` slot for the given exchange: a stateless
    codec passes the per-worker local state through untouched; a
    *stateful* codec (the ``ef:`` error-feedback wrapper) pairs it with
    the stacked ``(K, update_len)`` per-worker codec-state carry — the
    residual every round's encode reads and rewrites. The mirror image
    of :func:`init_exchange_state` widening ``shared`` for ``stale``."""
    codec = ExchangeConfig.parse(exchange).scheme.codec
    if not getattr(codec, "stateful", False):
        return local
    return local, jnp.stack([codec.init_state(update_len)] * K)


def unwrap_local_state(exchange, local):
    """The bare per-worker local state, dropping the codec-state slot
    a stateful codec's run carries (the post-run counterpart of
    :func:`wrap_local_state`; identity for stateless codecs)."""
    codec = ExchangeConfig.parse(exchange).scheme.codec
    return local[0] if getattr(codec, "stateful", False) else local


def _masked_apply(algo: "RoundAlgorithm", shared, agg, idx):
    """Apply one aggregate under its own round index ``idx``, masked
    out entirely when ``idx < 1`` (the queue slot still holds only the
    zero init — an algorithm's ``apply_update`` need not be the
    identity on a zero update, e.g. SGD's proximal step still moves,
    so the no-round apply must be masked rather than trusted)."""
    applied = algo.apply_update(shared, agg, jnp.maximum(idx, 1))
    return jax.tree_util.tree_map(
        lambda a, s: jnp.where(idx < 1, s, a), applied, shared)


def _queue_head(queue, i: int):
    return jax.tree_util.tree_map(lambda q: q[i], queue)


def _queue_push(queue, total):
    """Shift the pending queue one slot and append this round's
    aggregate (slot ``j`` holds the aggregate from ``j`` shifts ago +
    1 ... i.e. after round ``t`` the queue holds rounds ``t-k+1..t``,
    oldest first)."""
    return jax.tree_util.tree_map(
        lambda q, tot: jnp.concatenate([q[1:], tot[None]], axis=0),
        queue, total)


def _delayed_apply(algo: "RoundAlgorithm", shared, queue, t, k: int):
    """Apply the oldest pending aggregate — round ``t-k``'s — under its
    own round index (masked out while ``t <= k``, when no real
    aggregate has reached the queue head yet)."""
    return _masked_apply(algo, shared, _queue_head(queue, 0), t - k)


def _absorb_for_metric(algo: "RoundAlgorithm", shared, queue, t, k: int):
    """The metric must be the objective of ONE real iterate: fold the
    remaining pending aggregates (rounds ``t-k+1 .. t-1``) into a
    metric-only copy of the shared state so it is absorbed through
    round ``t-1`` — exactly the iterate the round-``t-1`` local state
    pairs with. A no-op at ``k=1`` (bit-identity with the pre-bounded
    stale mode)."""
    for i in range(1, k):
        shared = _masked_apply(algo, shared, _queue_head(queue, i),
                               t - k + i)
    return shared


def _make_flush(algo: "RoundAlgorithm", mode: ExchangeMode) -> Callable:
    """``flush(shared_state, t) -> shared``: absorb every pending
    aggregate left over from the last executed round ``t`` (identity in
    sync mode). After round ``t`` the queue holds the aggregates of
    rounds ``t-k+1 .. t`` oldest-first; each is applied under its own
    round index, masked out for slots that never saw a real round
    (``t < k``). Without the flush a short stale run would silently
    drop its trailing updates — the off-by-one the single-round
    sync-vs-stale regression test pins."""
    if not mode.stale:
        return lambda shared, t: shared
    k = mode.k

    @jax.jit
    def flush(shared_state, t):
        shared, queue = shared_state
        for i in range(k):
            shared = _masked_apply(algo, shared, _queue_head(queue, i),
                                   t - (k - 1) + i)
        return shared

    return flush


def finish_run(round_fn: Callable, shared, last_t: int):
    """The one post-run epilogue every trainer loop shares: absorb the
    pending aggregate from the last executed round (``last_t`` is its
    1-based index; 0 means no round ran, so there is nothing pending
    and the bare shared state is unwrapped as-is)."""
    if last_t > 0:
        return round_fn.flush(shared, last_t)
    return shared[0] if round_fn.mode.stale else shared


# ---------------------------------------------------------------------------
# the algorithm protocol
# ---------------------------------------------------------------------------
class RoundAlgorithm(Protocol):
    """What one algorithm plugs into the generic round drivers.

    ``data``   tuple of ``(K, ...)`` stacked arrays, partitioned on the
               leading worker axis (column blocks for CoCoA/SCD, row
               blocks for SGD).
    ``local``  ``(K, L_local)`` per-worker persistent state (alpha
               blocks; empty ``(K, 0)`` when the algorithm has none).
    ``shared`` replicated state (the residual ``w`` / the model
               ``alpha``).
    """

    def local_step(self, data_k, local_k, shared, key, t):
        """One worker's round: returns ``(update, local_new)`` where
        ``update`` is the 1-D vector to be all-reduced."""
        ...

    def apply_update(self, shared, total_update, t):
        """New shared state from the all-reduced update (round ``t``)."""
        ...

    def local_metric(self, data_k, local_k, shared_new):
        """Per-worker scalar metric contribution (summed across workers)."""
        ...

    def finalize_metric(self, shared_new, metric_sum):
        """Round metric (e.g. the primal objective) from the summed
        per-worker contributions."""
        ...


# ---------------------------------------------------------------------------
# generic round drivers
# ---------------------------------------------------------------------------
def _builder_exchange(exchange, *, scheme, mode, owner: str,
                      K: int) -> ExchangeConfig:
    """Resolve a driver builder's exchange arguments: the unified
    ``exchange`` value (ExchangeConfig / CommScheme / spec string) plus
    the deprecated ``scheme=`` / ``mode=`` keyword aliases."""
    if exchange is None:
        if scheme is None:
            raise TypeError(f"{owner}() needs an exchange spec (an "
                            f"ExchangeConfig, a CommScheme, or a spec "
                            f"string like 'compressed:int4/stale:k=2')")
        warn_deprecated(f"{owner}(scheme=...) is deprecated; pass the "
                        f"scheme as the positional exchange spec",
                        stacklevel=4)
        exchange = scheme
    elif scheme is not None:
        raise TypeError(f"{owner}() got both an exchange spec and the "
                        f"deprecated scheme= alias")
    ex = ExchangeConfig.parse(exchange)
    if mode is not None:
        warn_deprecated(f"{owner}(mode=...) is deprecated; fold the mode "
                        f"into the exchange spec (e.g. "
                        f"'{ex.scheme.name}/stale:k=2')", stacklevel=4)
        parsed = ExchangeMode.parse(mode)
        if ex.mode.stale and parsed != ex.mode:
            raise ValueError(f"{owner}(): mode={parsed.spec!r} conflicts "
                            f"with exchange={ex.spec!r}")
        import dataclasses as _dc
        ex = _dc.replace(ex, mode=parsed)
    ex.membership.check_workers(K)
    return ex


def _freeze_dropped(local_new, local_old, mask):
    """Freeze dropped workers' persistent local state: a worker that is
    absent this round keeps its pre-round state verbatim (``mask`` is
    the (K,)-or-scalar live mask, broadcast over the state's trailing
    axis)."""
    m = mask[..., None] if jnp.ndim(local_new) > jnp.ndim(mask) else mask
    return jnp.where(m > 0, local_new, local_old)


def build_virtual_round(algo: RoundAlgorithm, exchange=None, data=None,
                        *, K: int, use_map: bool = False,
                        mode=None, scheme=None) -> Callable:
    """K *virtual* workers on however many real devices exist.

    ``exchange`` is an :class:`ExchangeConfig`, a :class:`CommScheme`,
    or a spec string (``"compressed:int4/stale:k=2/drop:1@5"``); the
    keyword ``scheme=`` / ``mode=`` spellings are deprecated aliases.

    Returns ``round_fn(local, shared, key, t) -> (local_new,
    shared_new, metric)``. The data reaches the jitted round
    (``round_fn.jitted``) as its first argument, never as a closure
    constant, so the program text does not grow with the data;
    ``round_fn.lower(local, shared, key, t)`` lowers it ahead of time.
    ``use_map`` runs workers with ``lax.map``
    instead of ``vmap`` (needed for interpret-mode Pallas solvers).
    Under a stale mode the ``shared`` slot is the ``(shared, queue)``
    pair from :func:`init_exchange_state`: workers compute against
    state absorbed through round ``t-1-k``, the oldest pending
    aggregate is applied alongside, and this round's aggregate joins
    the back of the queue. ``round_fn.flush`` absorbs the whole queue
    after the last round. Under a *stateful* codec (``ef:``) the
    ``local`` slot is the ``(local, codec_state)`` pair from
    :func:`wrap_local_state`: the residual advances at encode time
    every round, orthogonally to the stale queue (which only delays
    the aggregate's *apply*). Workers dropped by the membership
    schedule contribute exact-zero updates (zeroed before codec
    encode — residual included) and their local state AND residual are
    frozen; when the algorithm averages over workers
    (``live_reweight``) the aggregate is rescaled by ``K / K_live``.
    Straggler profiles never enter here — under a bulk-synchronous
    barrier they change wall-clock, not math.
    """
    ex = _builder_exchange(exchange, scheme=scheme, mode=mode,
                           owner="build_virtual_round", K=K)
    comm, xmode, membership = ex.scheme, ex.mode, ex.membership
    k = xmode.k
    stateful = bool(getattr(comm.codec, "stateful", False))
    reweight = (not membership.empty
                and getattr(algo, "live_reweight", False))

    @jax.jit
    def jitted(data, local, shared, key, t):
        if stateful:
            local, cstate = local
        if xmode.stale:
            shared, queue = shared
        keys = jax.random.split(key, K)
        with jax.named_scope("workers"):
            if use_map:
                upd, local_new = lax.map(
                    lambda args: algo.local_step(args[0], args[1], shared,
                                                 args[2], t),
                    (data, local, keys))
            else:
                upd, local_new = jax.vmap(
                    lambda d, l, k_: algo.local_step(d, l, shared, k_, t))(
                        data, local, keys)
        cstate_in = cstate if stateful else None
        if not membership.empty:
            mask = membership.live_mask(t, K)
            upd = upd * mask[:, None]
            local_new = _freeze_dropped(local_new, local, mask)
            if stateful:
                # a dropped worker contributes an exact-zero encode:
                # its residual is zeroed alongside the update (zero is
                # a codec fixed point) and frozen below, so it neither
                # leaks into the aggregate nor decays while absent
                cstate_in = cstate_in * mask[:, None]
        with jax.named_scope("exchange"):
            if stateful:
                total, cstate_new = comm.all_reduce_stacked(upd, cstate_in)
            else:
                total = comm.all_reduce_stacked(upd)
        if stateful and not membership.empty:
            cstate_new = _freeze_dropped(cstate_new, cstate, mask)
        if reweight:
            total = total * (K / jnp.maximum(jnp.sum(mask), 1.0))
        with jax.named_scope("apply"):
            if xmode.stale:
                shared_new = _delayed_apply(algo, shared, queue, t, k)
                shared_out = (shared_new, _queue_push(queue, total))
            else:
                shared_new = shared_out = algo.apply_update(shared, total, t)
        with jax.named_scope("metric"):
            if xmode.stale:
                # the metric must be the objective of ONE iterate: pair
                # the shared state absorbed through round t-1 (the
                # metric-only absorb of the still-pending aggregates)
                # with the ROUND-t-1 local state (for CoCoA, w =
                # A@alpha - b holds exactly for that pair). Mixing in
                # the round-t local state produces a value that is no
                # iterate's objective and can dip below p_star. Under
                # stale the recorded metric therefore lags one round —
                # the honest cost of the delayed apply.
                metric_shared = _absorb_for_metric(algo, shared_new, queue,
                                                   t, k)
                metric_local = local
            else:
                metric_shared, metric_local = shared_new, local_new
            metric_sum = jnp.sum(jax.vmap(
                lambda d, l: algo.local_metric(d, l, metric_shared))(
                    data, metric_local))
            metric = algo.finalize_metric(metric_shared, metric_sum)
        local_out = (local_new, cstate_new) if stateful else local_new
        return local_out, shared_out, metric

    def round_fn(local, shared, key, t=1):
        return jitted(data, local, shared, key, t)

    round_fn.jitted = jitted
    round_fn.lower = lambda local, shared, key, t=1: jitted.lower(
        data, local, shared, key, t)
    round_fn.exchange = ex
    round_fn.mode = xmode
    round_fn.stateful_codec = stateful
    round_fn.flush = _make_flush(algo, xmode)
    return round_fn


def build_sharded_round(algo: RoundAlgorithm, exchange=None, data=None,
                        mesh: Mesh = None, *, donate: bool = True,
                        mode=None, scheme=None) -> Callable:
    """Real distribution via ``shard_map`` over the mesh's single axis.

    ``exchange`` is an :class:`ExchangeConfig`, a :class:`CommScheme`,
    or a spec string; the keyword ``scheme=`` / ``mode=`` spellings are
    deprecated aliases. Returns ``round_fn(local, shared, key, t) ->
    (local_new, shared_new, metric)`` with ``local``/``shared`` donated;
    the jitted inner ``round_fn.jitted(data, keys, local, shared, t)``
    takes the data as an argument, like the virtual driver's, and
    ``round_fn.lower(local, shared, key, t)`` lowers it ahead of time.
    The mesh axis size must equal the worker count K (the leading dim
    of every ``data`` leaf and of ``local``). Under a stale
    mode the ``shared`` slot is the ``(shared, queue)`` pair — same
    delayed apply, same collectives (the wire traffic is
    mode-independent, which the drivers benchmark asserts against the
    HLO), same per-worker RNG as the virtual driver. Membership masks
    are evaluated redundantly per shard from the replicated round
    index — elementwise ops only, so the HLO collectives are
    membership-invariant too.
    """
    K = mesh.devices.size
    ex = _builder_exchange(exchange, scheme=scheme, mode=mode,
                           owner="build_sharded_round", K=K)
    comm, xmode, membership = ex.scheme, ex.mode, ex.membership
    k = xmode.k
    stateful = bool(getattr(comm.codec, "stateful", False))
    reweight = (not membership.empty
                and getattr(algo, "live_reweight", False))
    axis = mesh.axis_names[0]
    for leaf in jax.tree_util.tree_leaves(data):
        assert leaf.shape[0] == K, (leaf.shape, K)

    def shard_fn(data_sh, local_sh, keys_sh, shared, t):
        if stateful:
            local_sh, cstate_sh = local_sh
            cstate_k = cstate_sh[0]
        data_k = jax.tree_util.tree_map(lambda x: x[0], data_sh)
        local_k = local_sh[0]
        key_k = jax.random.wrap_key_data(keys_sh[0])
        if xmode.stale:
            shared, queue = shared
        with jax.named_scope("workers"):
            upd, local_new = algo.local_step(data_k, local_k, shared, key_k,
                                             t)
        cstate_in = cstate_k if stateful else None
        if not membership.empty:
            mask = membership.live_mask(t, K)
            mask_k = mask[lax.axis_index(axis)]
            upd = upd * mask_k
            local_new = _freeze_dropped(local_new, local_k, mask_k)
            if stateful:
                # same contract as the virtual driver: a dropped
                # worker's residual is zeroed before encode and frozen
                # after — exact-zero wire contribution, no decay
                cstate_in = cstate_in * mask_k
        with jax.named_scope("exchange"):
            if stateful:
                total, cstate_new = comm.all_reduce(upd, axis,
                                                    backend=ex.backend,
                                                    state=cstate_in)
            else:
                total = comm.all_reduce(upd, axis, backend=ex.backend)
        if stateful and not membership.empty:
            cstate_new = _freeze_dropped(cstate_new, cstate_k, mask_k)
        if reweight:
            total = total * (K / jnp.maximum(jnp.sum(mask), 1.0))
        with jax.named_scope("apply"):
            if xmode.stale:
                shared_new = _delayed_apply(algo, shared, queue, t, k)
                shared_out = (shared_new, _queue_push(queue, total))
            else:
                shared_new = shared_out = algo.apply_update(shared, total, t)
        with jax.named_scope("exchange"):
            local_new = comm.roundtrip_local_state(local_new, axis,
                                                   backend=ex.backend)
        with jax.named_scope("metric"):
            # stale pairs the lagged shared state with the round-t-1
            # local state so the metric is a real iterate's objective
            # (see build_virtual_round) — and matches it round for round
            if xmode.stale:
                metric_shared = _absorb_for_metric(algo, shared_new, queue,
                                                   t, k)
                metric_local = local_k
            else:
                metric_shared, metric_local = shared_new, local_new
            metric_sum = lax.psum(algo.local_metric(data_k, metric_local,
                                                    metric_shared), axis)
            metric = algo.finalize_metric(metric_shared, metric_sum)
        local_out = ((local_new[None], cstate_new[None]) if stateful
                     else local_new[None])
        return local_out, shared_out, metric

    data_specs = jax.tree_util.tree_map(lambda _: P(axis), data)
    sharded = compat.shard_map(
        shard_fn, mesh,
        in_specs=(data_specs, P(axis), P(axis), P(None), P()),
        out_specs=(P(axis), P(None), P()))

    # data is an argument, never a closure constant: a captured array
    # is embedded in the program text (which then grows with the data),
    # and jit forbids capturing the global arrays of a multi-process run
    @functools.partial(jax.jit, donate_argnums=(2, 3) if donate else ())
    def jitted(data_arg, keys, local, shared, t):
        return sharded(data_arg, local, keys, shared, t)

    def split_keys(key):
        # same per-worker key derivation as the virtual driver, so the
        # two paths follow the same trajectory; computed OUTSIDE the
        # jitted round so XLA does not partition the threefry split into
        # spurious u32 collectives (which would pollute the HLO traffic
        # the byte accounting is checked against)
        return jax.random.key_data(jax.random.split(key, K))

    def round_fn(local, shared, key, t=1):
        return jitted(data, split_keys(key), local, shared, t)

    # the jitted inner + key derivation, exposed for AOT lowering (HLO
    # collective-traffic inspection in benches/tests) and for callers
    # that place the data and keys themselves (launch.dist)
    round_fn.jitted = jitted
    round_fn.lower = lambda local, shared, key, t=1: jitted.lower(
        data, split_keys(key), local, shared, t)
    round_fn.split_keys = split_keys
    round_fn.mesh = mesh
    round_fn.exchange = ex
    round_fn.mode = xmode
    round_fn.stateful_codec = stateful
    round_fn.flush = _make_flush(algo, xmode)
    return round_fn


def place_data(mesh: Mesh, data, axis: str | None = None):
    """Device-put the ``(K, ...)`` data leaves partitioned over the
    worker axis, once, before the sharded round is built: an array left
    on one device would be moved to the mesh on every round."""
    sharding = NamedSharding(mesh, P(axis or mesh.axis_names[0]))
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding),
                                  data)


def place_state(mesh: Mesh, local, shared, axis: str | None = None):
    """Device-put ``(local, shared)`` for the sharded driver: ``local``
    partitioned over the worker axis, ``shared`` replicated (``shared``
    may be the stale mode's ``(shared, pending)`` pair — every leaf is
    replicated)."""
    axis = axis or mesh.axis_names[0]
    local = jax.device_put(local, NamedSharding(mesh, P(axis)))
    shared = jax.device_put(shared, NamedSharding(mesh, P(None)))
    return local, shared
