"""Multi-device tests (subprocess with faked host devices): shard_map
CoCoA driver, the sync/stale exchange-mode contract (all staleness
bounds k), elastic worker membership, expert-parallel MoE,
local-update rounds, and a dry-run smoke on the production mesh —
plus the in-process codec round-trip property test over ALL wire codecs
(f32 / int8 / packed int4; hypothesis when installed, a deterministic
seed battery otherwise; NOT a module-wide importorskip, so the rest of
this file always runs).
"""
import functools
import os
import subprocess
import sys

import numpy as np
import pytest

# hypothesis is a dev extra (CI installs it via .[dev]); without it the
# property test below degrades to a fixed battery of generated examples
# instead of skipping, so the quantizer contract is always exercised
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(py: str, ndev: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# ---------------------------------------------------------------------------
# codec round-trip property test, ALL codecs (in-process; hypothesis
# optional)
# ---------------------------------------------------------------------------
CODEC_NAMES = ("f32", "int8", "int4", "int2", "topk(r=0.125)",
               "ef:int4", "ef:int2", "ef:topk(r=0.125)")


@functools.cache
def _codec_paths(codec_name: str):
    """The execution paths of one codec's encode/decode round-trip, all
    JITTED (as the drivers run them; jit re-specializes per input shape
    on its own): the vmap stacked path, the per-shard shard_map path on
    a 1-device ``workers`` axis (the 4-device variant is covered by
    ``test_compressed_quantizer_bit_identical_across_drivers`` below),
    and the aggregate each mode applies. Eager execution is
    deliberately NOT a reference here — XLA may lower the division by
    the absmax scale differently than op-by-op dispatch, and the
    drivers' contract is jitted-vs-jitted."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.comm import get_codec
    from repro.core.distributed import CommScheme
    from repro.utils import compat

    codec = get_codec(codec_name)

    @jax.jit
    def vmap_path(d):
        parts = jax.vmap(codec.encode)(d)
        return codec.decode_stacked(parts, d.shape[1])

    mesh = compat.make_mesh((1,), ("workers",))
    shard_path = jax.jit(compat.shard_map(
        lambda d: codec.decode(codec.encode(d[0]), d.shape[-1])[None],
        mesh, in_specs=P("workers"), out_specs=P("workers")))
    agg_path = jax.jit(
        CommScheme.parse(f"compressed:{codec_name}").all_reduce_stacked)
    # the aggregate reference restates each codec's reduction contract:
    # quantizing codecs accumulate SEQUENTIALLY in canonical worker
    # order behind the _no_fma guard (the fused decode+reduce oracle in
    # repro.kernels.dequant), everything else is the plain jnp.sum
    if codec_name.removeprefix("ef:") in ("int8", "int4", "int2"):
        from repro.kernels.dequant import _no_fma

        def _seq_sum(rows):
            acc = _no_fma(rows[0])
            for k in range(1, rows.shape[0]):
                acc = acc + _no_fma(rows[k])
            return acc
        sum_path = jax.jit(_seq_sum)
    else:
        sum_path = jax.jit(lambda rows: jax.numpy.sum(rows, axis=0))
    scales_path = jax.jit(lambda d: jax.vmap(codec.encode)(d)[-1])
    return vmap_path, shard_path, agg_path, sum_path, scales_path


def _roundtrip_bound(codec_name: str, scales: np.ndarray) -> np.ndarray:
    """Per-row elementwise error bound of ``decode(encode(x))``.

    * ``f32``  — the identity: exact.
    * ``int8`` — scale/2: absmax scaling puts every entry inside
      [-127, 127]*scale, so clipping never bites and the only error is
      round-to-nearest.
    * ``int4`` — scale/2 likewise (scale = absmax/7.5, the 15-level
      grid over [-absmax, absmax]): the bound equals absmax/15, which
      is ~8.5x the int8 codec's scale — the price of packing two
      elements per byte.
    * ``int2`` — scale/2 again (scale = absmax * 2/3, the ternary
      grid): the same clip-at-the-extreme argument as int4.
    * ``topk`` — kept entries decode exactly; every dropped entry
      satisfies |x| <= threshold (the k-th largest magnitude, the
      codec's "scale" wire part), so the threshold IS the bound.
    * ``ef:<base>`` — the stateless entry point encodes with a zero
      residual, i.e. exactly the base codec: the base codec's bound.

    The f32 divide/multiply round-trip gets a 1-ulp-ish allowance.
    """
    codec_name = codec_name.removeprefix("ef:")
    if codec_name == "f32":
        return np.zeros_like(scales)[:, None]
    if codec_name.startswith("topk"):
        return scales[:, None] * (1 + 1e-5) + 1e-30
    return 0.5 * scales[:, None] * (1 + 1e-5) + 1e-30


def _check_codec_roundtrip(codec_name: str, dv_np: np.ndarray):
    """The codec contract on one (K, L) update stack: elementwise
    round-trip error bounded by the codec's grid (see
    ``_roundtrip_bound``), zero rows decoding to exact zeros, and the
    vmap path bit-identical to the per-shard shard_map path (both for
    the per-worker vectors and for the aggregate the round applies)."""
    import jax.numpy as jnp

    dv = jnp.asarray(dv_np, jnp.float32)
    (vmap_path, shard_path, agg_path, sum_path,
     scales_path) = _codec_paths(codec_name)
    deq = vmap_path(dv)
    s = (np.asarray(scales_path(dv)) if codec_name != "f32"
         else np.zeros(dv.shape[0], np.float32))
    err = np.abs(np.asarray(deq) - np.asarray(dv))
    bound = _roundtrip_bound(codec_name, s)
    assert (err <= bound).all(), (
        f"{codec_name}: round-trip error {err.max()} exceeds the grid "
        f"bound (worst scale {s.max()})")
    # an all-zero worker row must decode to EXACT zeros — the explicit
    # guarantee of every codec (guarded scale, symmetric grid with 0)
    zero_rows = ~np.any(dv_np, axis=1)
    assert (np.asarray(deq)[zero_rows] == 0).all(), (
        f"{codec_name}: zero update decoded to nonzero values")
    # bit-identity with the shard_map path, per worker row
    shard_rows = [shard_path(row[None]) for row in dv]
    for k, row in enumerate(shard_rows):
        assert np.array_equal(np.asarray(row[0]), np.asarray(deq[k])), \
            f"{codec_name} worker {k}: vmap and shard_map dequants " \
            f"differ bitwise"
    # ... and for the aggregate the compressed exchange applies
    agg_v = agg_path(dv)
    agg_s = sum_path(jnp.concatenate(shard_rows, axis=0))
    assert np.array_equal(np.asarray(agg_v), np.asarray(agg_s)), \
        f"{codec_name}: aggregate drift between vmap and shard_map paths"


def _check_all_codecs(dv_np: np.ndarray):
    for codec_name in CODEC_NAMES:
        _check_codec_roundtrip(codec_name, dv_np)


def _random_update_stack(seed: int) -> np.ndarray:
    """A (4, 64) f32 update stack with per-worker magnitudes swept over
    ~40 decades (denormal-adjacent through 1e20), plus exact zeros."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((4, 64)).astype(np.float32)
    exps = rng.uniform(-20.0, 20.0, size=(4, 1)).astype(np.float32)
    dv = base * (10.0 ** exps)
    if seed % 3 == 0:
        dv[seed % 4] = 0.0          # an all-zero worker update
    if seed % 4 == 0:
        dv[0, seed % 64] = 0.0      # sparse zeros inside a row
    return dv.astype(np.float32)


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_codec_roundtrip_property(seed):
        _check_all_codecs(_random_update_stack(seed))
else:
    @pytest.mark.parametrize("seed", range(30))
    def test_codec_roundtrip_property(seed):
        _check_all_codecs(_random_update_stack(seed))


def test_codec_roundtrip_edge_values():
    """Exact edge cases the random sweep may miss: all-zero stacks, a
    single huge entry, values straddling the int8 clip boundary, and
    single-element updates (odd length: the int4 packer's padded
    nibble)."""
    _check_all_codecs(np.zeros((4, 64), np.float32))
    spike = np.zeros((4, 64), np.float32)
    spike[1, 3] = 3e38
    _check_all_codecs(spike)
    ramp = np.tile(np.linspace(-1.0, 1.0, 64, dtype=np.float32), (4, 1))
    _check_all_codecs(ramp * 127.49)
    _check_all_codecs(np.asarray([[2.5], [-1e-8], [0.0], [3e38]],
                                 np.float32))
    _check_all_codecs(np.ones((1, 1), np.float32))


def test_int2_pack_layout_and_wire_bytes():
    """The packed int2 wire format: ceil(L/4) uint8 payload under
    split-quarter pairing (element i shares a byte with i + q, i + 2q,
    i + 3q for q = ceil(L/4), biased codes q+2 in two-bit lanes), plus
    the 4-byte scale."""
    import jax
    import jax.numpy as jnp

    from repro.comm import get_codec

    codec = get_codec("int2")
    for L in (1, 2, 3, 7, 64, 97):
        dv = jnp.asarray(np.linspace(-1, 1, L), jnp.float32)
        packed, scale = jax.jit(codec.encode_ref)(dv)
        quarter = -(-L // 4)
        assert packed.shape == (quarter,) and packed.dtype == jnp.uint8
        assert codec.wire_bytes(L) == quarter + 4
        q = np.round(np.asarray(dv) / float(scale)).clip(-1, 1).astype(int)
        q = np.concatenate([q, np.zeros(4 * quarter - L, int)]) + 2
        rows = q.reshape(4, quarter)
        expect = (rows[0] | (rows[1] << 2) | (rows[2] << 4)
                  | (rows[3] << 6))
        assert (np.asarray(packed) == expect).all(), L


def test_topk_wire_format_and_threshold():
    """topk's wire tuple: exact f32 values + int32 indices of the k
    largest-magnitude entries, threshold (the k-th magnitude) last.
    Decode scatters the values and drops nothing above the threshold
    (on honest wire data the threshold mask is the identity)."""
    import jax.numpy as jnp

    from repro.comm import get_codec

    codec = get_codec("topk(r=0.125)")
    dv = jnp.asarray([0.0, -5.0, 1.0, 0.25, 3.0, -0.5, 0.0, 2.0,
                      -1.5, 0.125, 0.0, 4.0, -0.25, 0.75, 0.0, -3.5],
                     jnp.float32)
    values, idx, thr = codec.encode(dv)       # k = ceil(0.125*16) = 2
    assert values.shape == (2,) and idx.dtype == jnp.int32
    assert set(np.asarray(idx).tolist()) == {1, 11}   # -5.0 and 4.0
    assert float(thr) == 4.0
    dec = codec.decode((values, idx, thr), 16)
    expect = np.zeros(16, np.float32)
    expect[1], expect[11] = -5.0, 4.0
    assert np.array_equal(np.asarray(dec), expect)
    # r is clamped so k never exceeds L
    assert get_codec("topk(r=1)").wire_bytes(3) == 8 * 3 + 4


def test_int4_pack_layout_and_wire_bytes():
    """The packed int4 wire format: ceil(L/2) uint8 payload under
    split-half pairing (element i shares a byte with element
    i + ceil(L/2)), plus the 4-byte scale — the formula the byte model
    charges."""
    import jax
    import jax.numpy as jnp

    from repro.comm import get_codec

    codec = get_codec("int4")
    for L in (1, 2, 7, 64, 97):
        dv = jnp.asarray(np.linspace(-1, 1, L), jnp.float32)
        packed, scale = jax.jit(codec.encode_ref)(dv)
        assert packed.shape == ((L + 1) // 2,) and packed.dtype == jnp.uint8
        assert codec.wire_bytes(L) == (L + 1) // 2 + 4
        half = (L + 1) // 2
        q = np.round(np.asarray(dv) / float(scale)).clip(-7, 7).astype(int)
        q = np.concatenate([q, np.zeros(2 * half - L, int)])
        expect = (q[:half] + 8) | ((q[half:] + 8) << 4)
        assert (np.asarray(packed) == expect).all(), L


def test_quantize_pack_kernel_bit_identical_to_oracle():
    """The fused Pallas quantize+pack kernel (interpret mode off-TPU)
    must be BIT-identical to the jitted jnp oracle — payload and scale
    — for both codecs, across lengths exercising lane padding and the
    odd-length int4 tail."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import (quantize_pack_int2, quantize_pack_int2_ref,
                               quantize_pack_int4, quantize_pack_int4_ref,
                               quantize_pack_int8, quantize_pack_int8_ref)

    pairs = ((jax.jit(quantize_pack_int8_ref), quantize_pack_int8),
             (jax.jit(quantize_pack_int4_ref), quantize_pack_int4),
             (jax.jit(quantize_pack_int2_ref), quantize_pack_int2))
    for L in (1, 2, 7, 96, 128, 257):
        for seed in range(3):
            r = np.random.default_rng(1000 * L + seed)
            dv = jnp.asarray(
                r.standard_normal(L) * 10.0 ** r.uniform(-8, 8),
                jnp.float32)
            for ref_fn, ker_fn in pairs:
                p_r, s_r = ref_fn(dv)
                p_k, s_k = ker_fn(dv)
                assert np.array_equal(np.asarray(p_r), np.asarray(p_k)), (
                    L, seed, ker_fn.__name__)
                assert float(s_r) == float(s_k), (L, seed)
        z = jnp.zeros((L,), jnp.float32)
        for ref_fn, ker_fn in pairs:
            p_r, s_r = ref_fn(z)
            p_k, s_k = ker_fn(z)
            assert np.array_equal(np.asarray(p_r), np.asarray(p_k))
            assert float(s_r) == float(s_k) == 1.0  # the zero guard


def test_compressed_int8_bit_identical_to_legacy_quantizer():
    """Regression pin on the codec refactor: ``compressed:int8`` (and
    its bare ``compressed`` alias) must aggregate BIT-identically to
    the pre-codec quantizer (``scale = absmax/127 + 1e-30`` inline in
    core/distributed.py) for any nonzero input — the refactor moved
    the int8 path, it must not have changed it. The fused decode+reduce
    rework replaced the legacy ``jnp.sum`` over the stacked f32 decode
    with SEQUENTIAL accumulation in canonical worker order (the
    ``decode_stacked_ref`` oracle contract), so the legacy reference is
    restated in that order here — same quantizer, same values, pinned
    reduction sequence."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import CommScheme
    from repro.kernels.dequant import _no_fma

    @jax.jit
    def legacy_stacked(updates):
        def q1(dv):
            scale = jnp.max(jnp.abs(dv)) / 127.0 + 1e-30
            q = jnp.clip(jnp.round(dv / scale), -127, 127).astype(jnp.int8)
            return q, scale
        q, scale = jax.vmap(q1)(updates)
        stack = q.astype(jnp.float32) * scale[:, None]
        acc = _no_fma(stack[0])
        for k in range(1, stack.shape[0]):
            acc = acc + _no_fma(stack[k])
        return acc

    aliased = jax.jit(CommScheme.parse("compressed").all_reduce_stacked)
    named = jax.jit(CommScheme.parse("compressed:int8").all_reduce_stacked)
    for seed in range(20):
        dv = jnp.asarray(_random_update_stack(seed), jnp.float32)
        want = np.asarray(legacy_stacked(dv))
        assert np.array_equal(want, np.asarray(aliased(dv))), seed
        assert np.array_equal(want, np.asarray(named(dv))), seed


def test_compressed_alias_trajectory_bit_identical():
    """End-to-end regression: a CoCoA run under the bare ``compressed``
    scheme and under the explicit ``compressed:int8`` spelling must
    produce bit-identical iterates (the alias is the same codec object,
    not a second implementation)."""
    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.data import make_glm_data

    A, b, _ = make_glm_data(m=64, n=128, density=0.3, seed=3)
    finals = {}
    for scheme in ("compressed", "compressed:int8"):
        tr = CoCoATrainer(CoCoAConfig(K=4, H=32, seed=0,
                                      exchange=scheme), A, b)
        tr.run(6, record_every=6)
        finals[scheme] = (tr.alpha_final, tr.w_final)
    assert np.array_equal(finals["compressed"][0],
                          finals["compressed:int8"][0])
    assert np.array_equal(finals["compressed"][1],
                          finals["compressed:int8"][1])


def test_cocoa_sharded_matches_virtual():
    _run("""
import numpy as np, jax
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer
A, b, _ = make_glm_data(m=128, n=256, density=0.3, seed=1)
cfg = CoCoAConfig(K=8, H=64, seed=3)
t1 = CoCoATrainer(cfg, A, b); h1 = t1.run(rounds=20, record_every=20)
t2 = CoCoATrainer(cfg, A, b); h2 = t2.run_sharded(rounds=20, record_every=20)
# identical algorithm, identical rng -> identical trajectories
assert abs(h1.primal[-1] - h2.primal[-1]) / abs(h1.primal[-1]) < 1e-4, (h1.primal, h2.primal)
print("OK")
""")


def test_cocoa_kernel_solver_matches_ref_both_drivers():
    """The Pallas solver on the lane-tiled stack (m = 200, not a
    multiple of 128): ``run`` and ``run_sharded`` record the same
    history, and it is ``scd_ref``'s, round by round."""
    _run("""
import numpy as np
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer
A, b, _ = make_glm_data(m=200, n=96, density=0.3, seed=4)
hists = {}
for solver in ("scd_kernel", "scd_ref"):
    cfg = CoCoAConfig(K=4, H=24, solver=solver, seed=5)
    hists[solver, "run"] = CoCoATrainer(cfg, A, b).run(rounds=6)
    hists[solver, "run_sharded"] = CoCoATrainer(cfg, A, b).run_sharded(
        rounds=6)
ref = hists["scd_ref", "run"].primal
for key, h in hists.items():
    assert h.rounds == list(range(1, 7)), (key, h.rounds)
    np.testing.assert_allclose(h.primal, ref, rtol=1e-4, err_msg=str(key))
print("OK")
""", ndev=4)


def _round_text(make_round, n: int) -> str:
    """Lowered program text of one CoCoA round on an (m=64, n) problem."""
    import jax

    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.data import make_glm_data

    A, b, _ = make_glm_data(m=64, n=n, density=0.3, seed=0)
    tr = CoCoATrainer(CoCoAConfig(K=4, H=16), A, b)
    return make_round(tr).lower(*tr.init_state(), jax.random.key(0),
                                1).as_text()


def test_virtual_round_text_independent_of_data_size():
    """The virtual driver takes the data as an argument of its jitted
    round: 64x more data (1 MiB at n=4096, which a closure would embed
    as a constant) leaves the lowered program the same size."""
    small = _round_text(lambda tr: tr._round_fn, 64)
    big = _round_text(lambda tr: tr._round_fn, 4096)
    assert len(big) <= 1.05 * len(small), (len(small), len(big))


def test_sharded_round_text_independent_of_data_size():
    """Same contract for the sharded driver, on 4 faked devices."""
    _run("""
import sys
sys.path.insert(0, %r)
from test_distributed import _round_text
from repro.utils.compat import make_mesh
mesh = make_mesh((4,), ("workers",))
small = _round_text(lambda tr: tr.build_sharded_round(mesh), 64)
big = _round_text(lambda tr: tr.build_sharded_round(mesh), 4096)
assert len(big) <= 1.05 * len(small), (len(small), len(big))
print("OK")
""" % os.path.dirname(os.path.abspath(__file__)), ndev=4)


def test_cocoa_spark_faithful_extra_collectives():
    _run("""
import numpy as np, jax, jax.random as jr
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer
from repro.utils.hlo import parse_collectives
from repro.utils.compat import make_mesh
A, b, _ = make_glm_data(m=128, n=256, density=0.3, seed=1)
texts = {}
for scheme in ("persistent", "spark_faithful"):
    tr = CoCoATrainer(CoCoAConfig(K=8, H=32, exchange=scheme), A, b)
    mesh = make_mesh((8,), ("workers",))
    rf = tr.build_sharded_round(mesh)
    alpha, w = tr.init_state()
    low = rf.lower(alpha, w, jr.key(0), 1)
    texts[scheme] = parse_collectives(low.compile().as_text())
p, s = texts["persistent"], texts["spark_faithful"]
assert "all-gather" in s.by_kind and "all-gather" not in p.by_kind
assert s.total_operand_bytes > p.total_operand_bytes
print("OK")
""")


def test_driver_matrix_virtual_vs_sharded_all_algorithms():
    """The unified layer's contract: for every algorithm x comm scheme,
    the virtual (vmap) and sharded (shard_map) drivers follow the same
    trajectory (identical per-worker RNG; only reduction mechanics
    differ)."""
    _run("""
import numpy as np
from repro.data import make_glm_data
from repro.core import (CoCoAConfig, CoCoATrainer, MinibatchSCD,
                        MinibatchSGD, SGDConfig, COMM_SCHEMES)
A, b, _ = make_glm_data(m=96, n=256, density=0.2, zipf_a=1.1, seed=42)
def make(algo, scheme):
    if algo == "minibatch_sgd":
        return MinibatchSGD(SGDConfig(batch_frac=1.0, step_size=0.1,
                                      lam=1.0, K=4, seed=0,
                                      exchange=scheme), A, b)
    cfg = CoCoAConfig(K=4, H=64, exchange=scheme, seed=0)
    return (MinibatchSCD if algo == "minibatch_scd" else CoCoATrainer)(cfg, A, b)
for algo in ("cocoa", "minibatch_scd", "minibatch_sgd"):
    for scheme in COMM_SCHEMES:
        tv = make(algo, scheme)
        hv = (tv.run_workers(12, record_every=12)
              if algo == "minibatch_sgd" else tv.run(12, record_every=12))
        ts = make(algo, scheme)
        hs = ts.run_sharded(12, record_every=12)
        rel = abs(hv.primal[-1] - hs.primal[-1]) / abs(hv.primal[-1])
        assert rel < 1e-4, (algo, scheme, hv.primal, hs.primal)
print("OK")
""", ndev=4, timeout=560)


def test_single_round_stale_equals_sync_all_algorithms_both_drivers():
    """Regression pin on the delayed apply's off-by-one: with exactly
    one round there is nothing to be stale about — the flushed `stale`
    iterate must be IDENTICAL to the `sync` iterate for all 3 algorithms
    on both drivers, for EVERY staleness bound k (same per-worker RNG,
    same aggregate, applied once either way; the flush absorbs however
    many slots are pending). A stale run that drops or double-applies a
    pending aggregate fails this immediately. Multi-round trajectories
    must then genuinely diverge (the knob does something), and deeper k
    must diverge from k=1 too."""
    _run("""
import numpy as np
from repro.data import make_glm_data
from repro.core import (CoCoAConfig, CoCoATrainer, MinibatchSCD,
                        MinibatchSGD, SGDConfig)
A, b, _ = make_glm_data(m=96, n=256, density=0.2, zipf_a=1.1, seed=42)
def make(algo, mode):
    if algo == "minibatch_sgd":
        return MinibatchSGD(SGDConfig(batch_frac=1.0, step_size=0.1,
                                      lam=1.0, K=4, seed=0,
                                      exchange=mode), A, b)
    cfg = CoCoAConfig(K=4, H=64, seed=0, exchange=mode)
    return (MinibatchSCD if algo == "minibatch_scd" else CoCoATrainer)(cfg, A, b)
for algo in ("cocoa", "minibatch_scd", "minibatch_sgd"):
    for driver in ("virtual", "sharded"):
        def run1(tr, rounds=1):
            if driver == "sharded":
                return tr.run_sharded(rounds, record_every=1)
            return (tr.run_workers(rounds, record_every=1)
                    if algo == "minibatch_sgd"
                    else tr.run(rounds, record_every=1))
        ts = make(algo, "sync"); run1(ts)
        for stale in ("stale", "stale:k=2", "stale:k=3"):
            tt = make(algo, stale); run1(tt)
            assert np.array_equal(ts.alpha_final, tt.alpha_final), (
                algo, driver, stale, "alpha drift after 1 round")
            if algo != "minibatch_sgd":  # CoCoA-family: shared residual
                assert np.array_equal(ts.w_final, tt.w_final), (
                    algo, driver, stale, "w drift after 1 round")
    # with >1 round the delayed apply must actually change the
    # trajectory (otherwise the knob is a no-op), and k=2 must be a
    # genuinely deeper delay than k=1
    finals = {}
    for mode in ("sync", "stale", "stale:k=2"):
        tr = make(algo, mode)
        (tr.run_workers(5, record_every=5) if algo == "minibatch_sgd"
         else tr.run(5, record_every=5))
        finals[mode] = np.asarray(tr.alpha_final)
    assert not np.array_equal(finals["sync"], finals["stale"]), (
        algo, "stale trajectory identical to sync after 5 rounds")
    assert not np.array_equal(finals["stale"], finals["stale:k=2"]), (
        algo, "stale:k=2 trajectory identical to k=1 after 5 rounds")
print("OK")
""", ndev=4, timeout=560)


def test_stale_driver_agreement_and_same_collectives():
    """The exchange-mode contract on the sharded driver: under `stale`
    (any bound k) the virtual and sharded drivers still follow the same
    trajectory for every comm scheme, and staleness never changes what
    the collectives move — the optimized HLO's collective traffic is
    byte-for-byte the same as the sync round's."""
    _run("""
import numpy as np, jax.random as jr
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer, COMM_SCHEMES
from repro.utils.hlo import parse_collectives
from repro.utils.compat import make_mesh
A, b, _ = make_glm_data(m=96, n=256, density=0.2, zipf_a=1.1, seed=42)
mesh = make_mesh((4,), ("workers",))
def traffic(tr):
    rf = tr.build_sharded_round(mesh)
    local, shared = tr.init_state()
    txt = rf.lower(local, shared, jr.key(0), 1).compile().as_text()
    s = parse_collectives(txt)
    return {k: v[1] for k, v in s.by_kind.items()}
for scheme in COMM_SCHEMES:
    for stale in ("stale", "stale:k=2"):
        spec = scheme + "/" + stale
        tv = CoCoATrainer(CoCoAConfig(K=4, H=64, seed=0, exchange=spec),
                          A, b)
        hv = tv.run(8, record_every=8)
        ts = CoCoATrainer(CoCoAConfig(K=4, H=64, seed=0, exchange=spec),
                          A, b)
        hs = ts.run_sharded(8, record_every=8)
        rel = abs(hv.primal[-1] - hs.primal[-1]) / abs(hv.primal[-1])
        assert rel < 1e-4, (spec, hv.primal, hs.primal)
        t_sync = traffic(CoCoATrainer(CoCoAConfig(K=4, H=64, seed=0,
                                                  exchange=scheme), A, b))
        t_stale = traffic(CoCoATrainer(CoCoAConfig(K=4, H=64, seed=0,
                                                   exchange=spec), A, b))
        assert t_sync == t_stale, (spec, t_sync, t_stale)
print("OK")
""", ndev=4, timeout=560)


def test_elastic_membership_virtual_vs_sharded():
    """The elastic-membership contract: with workers dropping and
    rejoining at configured rounds the virtual and sharded drivers
    still follow the same trajectory (the live mask is applied
    identically inside both), including when composed with a staleness
    bound and a quantizing codec — and membership adds NO collectives
    to the compiled round (one compile serves every round; liveness is
    an elementwise mask, so the HLO traffic matches the always-live
    program byte-for-byte)."""
    _run("""
import dataclasses
import numpy as np, jax.random as jr
from repro.data import make_glm_data
from repro.core import (CoCoAConfig, CoCoATrainer, ExchangeConfig,
                        MembershipSchedule, MinibatchSGD, SGDConfig)
from repro.utils.hlo import parse_collectives
from repro.utils.compat import make_mesh
A, b, _ = make_glm_data(m=96, n=256, density=0.2, zipf_a=1.1, seed=42)
mesh = make_mesh((4,), ("workers",))
def make(algo, spec):
    if algo == "minibatch_sgd":
        return MinibatchSGD(SGDConfig(batch_frac=1.0, step_size=0.1,
                                      lam=1.0, K=4, seed=0,
                                      exchange=spec), A, b)
    return CoCoATrainer(CoCoAConfig(K=4, H=64, seed=0, exchange=spec),
                        A, b)
def traffic(tr):
    rf = tr.build_sharded_round(mesh)
    local, shared = tr.init_state()
    txt = rf.lower(local, shared, jr.key(0), 1).compile().as_text()
    return {k: v[1] for k, v in parse_collectives(txt).by_kind.items()}
CASES = (("cocoa", "persistent/drop:1@2-4"),
         ("cocoa", "compressed:int8/stale:k=2/drop:0@1-2"),
         ("minibatch_sgd", "persistent/drop:2@3"),
         ("minibatch_sgd", "compressed:int4/drop:1@2-4"))
for algo, spec in CASES:
    tv = make(algo, spec)
    hv = (tv.run_workers(8, record_every=8) if algo == "minibatch_sgd"
          else tv.run(8, record_every=8))
    ts = make(algo, spec)
    hs = ts.run_sharded(8, record_every=8)
    rel = abs(hv.primal[-1] - hs.primal[-1]) / abs(hv.primal[-1])
    assert rel < 1e-4, (algo, spec, hv.primal, hs.primal)
    # the drop must actually bite: trajectory differs from always-live
    base_spec = dataclasses.replace(ExchangeConfig.parse(spec),
                                    membership=MembershipSchedule())
    always = make(algo, base_spec)
    (always.run_workers(8, record_every=8) if algo == "minibatch_sgd"
     else always.run(8, record_every=8))
    assert not np.array_equal(np.asarray(tv.alpha_final),
                              np.asarray(always.alpha_final)), (algo, spec)
    # ... without adding or resizing any collective
    assert traffic(make(algo, spec)) == traffic(always), (algo, spec)
print("OK")
""", ndev=4, timeout=560)


def test_sharded_sgd_allreduce_n_vector_cocoa_m_vector():
    """Paper §5.4: mini-batch SGD all-reduces the n-dim gradient while
    CoCoA all-reduces the m-dim Delta v — more traffic whenever n > m,
    and it must be visible in the HLO."""
    _run("""
import re, jax, jax.random as jr
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer, MinibatchSGD, SGDConfig
from repro.utils.compat import make_mesh
m, n = 96, 256
A, b, _ = make_glm_data(m=m, n=n, density=0.2, seed=1)
mesh = make_mesh((4,), ("workers",))
def hlo(tr):
    rf = tr.build_sharded_round(mesh)
    local, shared = tr.init_state()
    return rf.lower(local, shared, jr.key(0), 1).compile().as_text()
coc = hlo(CoCoATrainer(CoCoAConfig(K=4, H=32), A, b))
sgd = hlo(MinibatchSGD(SGDConfig(K=4, step_size=0.1), A, b))
assert re.search(rf"f32\\[{m}\\]\\S* all-reduce", coc), "m-vector all-reduce missing"
assert not re.search(rf"f32\\[{n}\\]\\S* all-reduce", coc), "CoCoA must not move an n-vector"
assert re.search(rf"f32\\[{n}\\]\\S* all-reduce", sgd), "n-vector all-reduce missing"
assert not re.search(rf"f32\\[{m}\\]\\S* all-reduce", sgd), "SGD must not move an m-vector"
print("OK")
""", ndev=4)


def test_compressed_quantizer_bit_identical_across_drivers():
    """Both drivers call the ONE shared quantization helper, so the
    dequantized updates — and their aggregate — are bit-identical
    between the virtual and sharded paths."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.distributed import (CommScheme, quantize_update,
                                    dequantize_update)
from repro.utils.compat import make_mesh, shard_map
K, m = 4, 96
dv = jax.random.normal(jax.random.key(7), (K, m), jnp.float32)
dv = dv * (10.0 ** jnp.arange(-2, K - 2, dtype=jnp.float32))[:, None]
mesh = make_mesh((K,), ("workers",))
# per-worker dequantized updates: vmapped helper vs per-shard helper
q, s = jax.vmap(quantize_update)(dv)
virt = dequantize_update(q, s[:, None])
f = shard_map(lambda d: dequantize_update(*quantize_update(d[0]))[None],
              mesh, in_specs=P("workers"), out_specs=P("workers"))
shrd = jax.jit(f)(dv)
assert np.array_equal(np.asarray(virt), np.asarray(shrd)), "per-worker drift"
# the aggregated update the round actually applies
scheme = CommScheme.parse("compressed")
agg_v = scheme.all_reduce_stacked(dv)
g = shard_map(lambda d: scheme.all_reduce(d[0], "workers"), mesh,
              in_specs=P("workers"), out_specs=P(None))
agg_s = jax.jit(g)(dv)
assert np.array_equal(np.asarray(agg_v), np.asarray(agg_s)), "aggregate drift"
print("OK")
""", ndev=4)


def test_moe_sharded_matches_global():
    _run("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.models import layers as L
cfg = get_config("deepseek-v3-671b").reduced()
from repro.utils.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
p = L.init_moe(jax.random.key(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32) * 0.1
L.set_partitioning(dp=("data",), tp="model", mesh=mesh)
with mesh:
    y1, _ = jax.jit(lambda p, x: L.moe_apply(p, cfg, x))(p, x)
L.set_partitioning()
y2, _ = L.moe_apply(p, cfg, x)
d = float(jnp.max(jnp.abs(y1 - y2)))
assert d < 1e-5, d
print("OK")
""")


def test_local_updates_H1_sgd_equals_sync_dp():
    """With plain SGD, H=1 local updates == synchronous data parallelism
    (gradient averaging) — the paper's knob reduces to the baseline."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.optim import LocalUpdatesConfig, local_updates_round
from repro.utils.compat import make_mesh
mesh = make_mesh((4,), ("data",))
lr = 0.1
def loss(w, b):
    x, y = b
    return jnp.mean((x @ w - y) ** 2)
def sgd_step(w, o, b):
    g = jax.grad(loss)(w, b)
    return w - lr * g, o, {}
rng = np.random.default_rng(0)
X = jnp.asarray(rng.standard_normal((8, 4, 3)), jnp.float32)  # 8 shards*... (4 per shard? -> (4 shards,2,... )
X = jnp.asarray(rng.standard_normal((4, 1, 6, 3)), jnp.float32)  # (shards, H=1, batch, feat)
Y = jnp.asarray(rng.standard_normal((4, 1, 6)), jnp.float32)
w0 = jnp.zeros((3,))
# reference: one sync step on the full data
g_full = jax.grad(loss)(w0, (X.reshape(-1, 3), Y.reshape(-1)))
w_ref = w0 - lr * g_full
# local-updates H=1 via shard_map over data
def round_fn(w, Xs, Ys):
    def body(Xl, Yl, w):
        cfg = LocalUpdatesConfig(H=1)
        w2, _, _ = local_updates_round(sgd_step, w, {}, (Xl[0], Yl[0]), cfg, "data")
        return w2
    from repro.utils.compat import shard_map
    return shard_map(body, mesh,
        in_specs=(P("data"), P("data"), P(None)), out_specs=P(None))(Xs, Ys, w)
w_lu = jax.jit(round_fn)(w0, X, Y)
assert float(jnp.max(jnp.abs(w_lu - w_ref))) < 1e-6, (w_lu, w_ref)
print("OK")
""")


def test_local_updates_codec_delta_exchange():
    """The transformer local-SGD workload's compressed exchange: with a
    quantizing codec the delta exchange all-gathers encoded payloads
    and decodes+means locally. H=2 local SGD on a 4-shard toy problem:
    the int8 result must track the exact f32 pmean to the codec's grid
    error, int4 coarser but bounded, and an all-zero delta (lr=0) must
    come back EXACTLY zero — the codec layer's zero guarantee, end to
    end through shard_map."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import LocalUpdatesConfig, local_updates_round
from repro.utils.compat import make_mesh, shard_map
mesh = make_mesh((4,), ("data",))
def loss(w, b):
    x, y = b
    return jnp.mean((x @ w - y) ** 2)
def make_step(lr):
    def sgd_step(w, o, b):
        return w - lr * jax.grad(loss)(w, b), o, {}
    return sgd_step
rng = np.random.default_rng(0)
X = jnp.asarray(rng.standard_normal((4, 2, 6, 3)), jnp.float32)  # (shards, H=2, batch, feat)
Y = jnp.asarray(rng.standard_normal((4, 2, 6)), jnp.float32)
w0 = jnp.asarray(rng.standard_normal(3), jnp.float32)
def run(codec, lr):
    cfg = LocalUpdatesConfig(H=2, codec=codec)
    def body(Xl, Yl, w):
        w2, _, _ = local_updates_round(make_step(lr), w, {}, (Xl[0], Yl[0]),
                                       cfg, "data")
        return w2
    f = shard_map(body, mesh, in_specs=(P("data"), P("data"), P(None)),
                  out_specs=P(None))
    return jax.jit(f)(X, Y, w0)
w_f32 = run("f32", 0.05)
d_f32 = np.abs(np.asarray(w_f32) - np.asarray(w0)).max()
assert d_f32 > 0, "reference round did not move"
for codec, mult in (("int8", 1.0), ("int4", 17.0), ("int2", 85.0)):
    w_c = run(codec, 0.05)
    err = np.abs(np.asarray(w_c) - np.asarray(w_f32)).max()
    # the averaged delta's error is bounded by the mean of per-shard
    # grid errors; compare against the f32 delta magnitude with the
    # codec's grid-coarseness factor (int4 grid ~17x coarser)
    assert err <= 0.02 * mult * max(d_f32, 1e-9), (codec, err, d_f32)
# lr=0: every shard's delta is exactly zero -> the decoded mean must be
# exactly w0 under EVERY codec (the zero-input guarantee through the
# whole exchange)
for codec in ("f32", "int8", "int4", "int2", "topk(r=0.25)", "ef:int4"):
    w_z = run(codec, 0.0)
    assert np.array_equal(np.asarray(w_z), np.asarray(w0)), codec
print("OK")
""", ndev=4)


def test_local_updates_delta_bytes_match_hlo():
    """Satellite of the byte-model repair: lower ONE delta exchange per
    codec (sync_opt_state off to isolate it) and pin delta_wire_bytes
    against the HLO-derived bytes — the f32 pmean all-reduce, the
    quantized all-gathers, topk's live threshold gather (decode consumes
    it, so XLA cannot dead-code it away), and the ef: state threading
    all price exactly."""
    _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.optim.local_updates import (LocalUpdatesConfig, local_updates_round,
                                       delta_wire_bytes, init_delta_codec_state)
from repro.utils.compat import make_mesh, shard_map
from repro.analysis.graph import lift_hlo
from repro.analysis.traffic import derived_round_traffic

K = 4
mesh = make_mesh((K,), ("data",))

def step_fn(p, o, mb):
    g = jax.tree.map(lambda x: x * 0.01 + mb["x"].sum() * 0, p)
    return jax.tree.map(lambda a, b: a - 0.1 * b, p, g), o, {"loss": mb["x"].sum()}

params = {"w": jnp.ones((96,)) * 0.3, "b": jnp.ones((33,)) * -0.2}
batches = {"x": jnp.zeros((K, 2, 8))}

class Duck:
    backend = None
    class scheme: transport = "compressed"

for codec in ("f32", "int8", "int4", "int2", "topk(r=0.125)",
              "ef:int4", "ef:int2", "ef:topk(r=0.125)"):
    cfg = LocalUpdatesConfig(H=2, codec=codec, sync_opt_state=False)
    cstate = init_delta_codec_state(params, cfg)
    if cstate is None:
        def run(p, b):
            pH, oH, m = local_updates_round(step_fn, p, {}, b, cfg, "data")
            return pH, m["loss"].sum()[None]
        f = shard_map(run, mesh, in_specs=(P(), P("data")),
                      out_specs=(P(), P("data")))
        hlo = jax.jit(f).lower(params, batches).compile().as_text()
    else:
        cstateK = jax.tree.map(lambda s: jnp.stack([s] * K), cstate)
        def run(p, b, cs):
            cs = jax.tree.map(lambda x: x[0], cs)
            pH, oH, m, cs = local_updates_round(step_fn, p, {}, b, cfg,
                                                "data", codec_state=cs)
            return pH, m["loss"].sum()[None], jax.tree.map(
                lambda x: x[None], cs)
        f = shard_map(run, mesh, in_specs=(P(), P("data"), P("data")),
                      out_specs=(P(), P("data"), P("data")))
        hlo = jax.jit(f).lower(params, batches, cstateK).compile().as_text()
    derived = derived_round_traffic(lift_hlo(hlo), Duck, K)
    model = delta_wire_bytes(params, cfg, K)
    assert derived == model, (codec, derived, model)
print("OK")
""", ndev=4)


def test_ef_wrapper_residual_semantics():
    """EFWrapper unit contracts: (a) the zero-residual entry point is
    bitwise the base codec; (b) encode_with_state returns residual =
    (dv + state) - decode(parts); (c) iterating on a constant update
    keeps the residual bounded while the MEAN decoded update converges
    to the true value (the error is delayed, not destroyed) — where
    plain int4 holds a permanent bias on the same input."""
    import jax
    import jax.numpy as jnp

    from repro.comm import get_codec

    base = get_codec("int4")
    ef = get_codec("ef:int4")
    rng = np.random.default_rng(7)
    dv = jnp.asarray(rng.standard_normal(96) * 0.1, jnp.float32)
    for pb, pe in zip(base.encode(dv), ef.encode(dv)):
        assert np.array_equal(np.asarray(pb), np.asarray(pe))
    state = ef.init_state(96)
    assert state.shape == (96,) and not np.any(np.asarray(state))
    parts, new_state = jax.jit(ef.encode_with_state)(dv, state)
    expect = np.asarray(dv) - np.asarray(base.decode(parts, 96))
    assert np.allclose(np.asarray(new_state), expect, atol=1e-7)

    @jax.jit
    def step(state):
        parts, state = ef.encode_with_state(dv, state)
        return ef.decode(parts, 96), state

    decoded_sum = jnp.zeros(96)
    for t in range(200):
        dec, state = step(state)
        decoded_sum = decoded_sum + dec
        assert float(jnp.linalg.norm(state)) < 10.0, t  # bounded residual
    mean_err = float(jnp.max(jnp.abs(decoded_sum / 200 - dv)))
    plain_err = float(jnp.max(jnp.abs(base.decode(base.encode(dv), 96) - dv)))
    assert mean_err < 0.2 * plain_err, (mean_err, plain_err)


def test_stateful_codec_widens_local_slot():
    """wrap_local_state/unwrap_local_state: identity (the SAME object)
    for stateless codecs — the sync/f32 drivers are untouched by the
    EF machinery — and a (local, (K, L) zeros) pair for ef: codecs."""
    import jax.numpy as jnp

    from repro.core import distributed as dist

    local = jnp.ones((4, 7))
    for spec in ("persistent", "compressed:int4", "compressed:topk(r=0.5)"):
        assert dist.wrap_local_state(spec, local, 96, 4) is local
        assert dist.unwrap_local_state(spec, local) is local
    wrapped = dist.wrap_local_state("compressed:ef:int4", local, 96, 4)
    assert isinstance(wrapped, tuple) and wrapped[0] is local
    assert wrapped[1].shape == (4, 96) and not np.any(np.asarray(wrapped[1]))
    assert dist.unwrap_local_state("compressed:ef:int4", wrapped) is local


def test_ef_codec_lifts_int4_floor_virtual_driver():
    """The headline, at unit-test scale on the virtual driver: plain
    compressed:int4 floors well above the duality gap compressed:ef:int4
    reaches on the same problem/rounds — error feedback converts the
    biased grid's floor into convergence."""
    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.data import make_glm_data

    A, b, _ = make_glm_data(m=48, n=96, density=0.3, zipf_a=1.1, seed=3)

    def gap(exchange):
        tr = CoCoATrainer(CoCoAConfig(K=4, H=24, lam=1.0, solver="scd_ref",
                                      exchange=exchange, seed=0), A, b)
        return tr.run(rounds=40, record_every=40).subopt[-1]

    g_int4 = gap("compressed:int4")
    g_ef = gap("compressed:ef:int4")
    assert g_ef < 1e-3, g_ef
    assert g_int4 > 20 * g_ef, (g_int4, g_ef)


def test_ef_sharded_matches_virtual_under_regimes():
    """Codec-state threading through the sharded driver: ef:int4 under
    plain sync, bounded staleness, and elastic membership must track the
    virtual driver's trajectory bit-tight (the widened local slot rides
    the same wrap/unwrap path in both drivers)."""
    _run("""
import numpy as np
from repro.core import CoCoAConfig, CoCoATrainer
from repro.data import make_glm_data
A, b, _ = make_glm_data(m=48, n=96, density=0.3, zipf_a=1.1, seed=3)
for spec in ("compressed:ef:int4", "compressed:ef:int4/stale:k=2",
             "compressed:ef:int4/drop:1@2-4"):
    hv = CoCoATrainer(CoCoAConfig(K=4, H=24, lam=1.0, solver="scd_ref",
                                  exchange=spec, seed=0), A, b) \
        .run(rounds=10, record_every=2)
    hs = CoCoATrainer(CoCoAConfig(K=4, H=24, lam=1.0, solver="scd_ref",
                                  exchange=spec, seed=0), A, b) \
        .run_sharded(rounds=10, record_every=2)
    dp = np.max(np.abs(np.asarray(hv.primal) - np.asarray(hs.primal)))
    assert dp < 1e-5, (spec, dp)
print("OK")
""", ndev=4)


def test_dryrun_production_mesh_smoke():
    """The real deliverable-(e) path: tinyllama decode on the 16x16 and
    2x16x16 meshes must lower + compile in a 512-device subprocess.
    (`slow` tier — marked from the registry in conftest.py, not here.)"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "tinyllama-1.1b", "--shape", "decode_32k",
         "--both-meshes", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=560, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "all dry-runs OK" in out.stdout


def test_cocoa_compressed_int8_collective():
    """The compressed scheme's collective moves int8, not f32."""
    _run("""
import numpy as np, jax, jax.random as jr, re
from repro.data import make_glm_data
from repro.core import CoCoAConfig, CoCoATrainer
A, b, _ = make_glm_data(m=128, n=256, density=0.3, seed=1)
tr = CoCoATrainer(CoCoAConfig(K=8, H=32, exchange="compressed"), A, b)
from repro.utils.compat import make_mesh
mesh = make_mesh((8,), ("workers",))
rf = tr.build_sharded_round(mesh)
alpha, w = tr.init_state()
txt = rf.lower(alpha, w, jr.key(0), 1).compile().as_text()
assert re.search(r"s8\\[[0-9,]+\\][^ ]* all-gather", txt), "int8 all-gather missing"
h = tr.run_sharded(rounds=25, record_every=25)
assert h.subopt[-1] < 5e-2, h.subopt
print("OK")
""")
