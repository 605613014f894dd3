"""Bit-identity of the fused gather-side Pallas kernels against their
codec oracles (interpret mode on CPU — the same lowering contract the
comm layer relies on when it dispatches to the kernels on TPU), plus a
driver-level regression pinning the ``compressed:int4`` trajectory to
the sequential decode+reduce contract.

All comparisons are jitted-vs-jitted: XLA may lower an op-by-op eager
dispatch differently, and the contract pinned here is the one the
drivers execute."""
from __future__ import annotations

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.comm.codec import get_codec  # noqa: E402
from repro.kernels import (decode_reduce_int2, decode_reduce_int4,  # noqa: E402
                           decode_reduce_int8, decode_stacked_ref,
                           topk_select, topk_select_ref)

DECODE = {"int8": decode_reduce_int8,
          "int4": decode_reduce_int4,
          "int2": decode_reduce_int2}


@functools.cache
def _oracle(codec_name: str, length: int, mean: bool):
    """The jitted sequential-accumulation oracle from repro.kernels.ref
    (= the comm layer's off-TPU path)."""
    return jax.jit(lambda p, s: decode_stacked_ref(
        codec_name, (p, s), length, mean=mean))


def _gathered(codec_name: str, K: int, L: int, seed: int):
    """A (K, wire) payload + (K,) scales stack as the all-gather ships
    it: each worker row encoded independently."""
    codec = get_codec(codec_name)
    rng = np.random.default_rng(seed)
    parts = [codec.encode(jnp.asarray(
        rng.standard_normal(L) * 10.0 ** rng.integers(-3, 4), jnp.float32))
        for _ in range(K)]
    return (jnp.stack([p for p, _ in parts]),
            jnp.stack([s for _, s in parts]))


@pytest.mark.parametrize("codec_name", sorted(DECODE))
@pytest.mark.parametrize("K", [1, 3, 4, 8])
@pytest.mark.parametrize("L", [1, 5, 96, 127, 128, 129, 1000])
def test_decode_reduce_bit_identical_to_oracle(codec_name, K, L):
    """Fused decode+reduce == the sequential jnp oracle, bitwise, for
    both the mean and the sum reduction, across packing-boundary and
    odd lengths."""
    payload, scales = _gathered(codec_name, K, L, seed=K * 1000 + L)
    for mean in (True, False):
        want = _oracle(codec_name, L, mean)(payload, scales)
        got = DECODE[codec_name](payload, scales, L, mean=mean)
        assert want.shape == got.shape == (L,)
        assert np.array_equal(np.asarray(want), np.asarray(got)), (
            f"{codec_name} K={K} L={L} mean={mean}: fused kernel is "
            f"not bit-identical to the oracle")


@pytest.mark.parametrize("codec_name", sorted(DECODE))
def test_decode_reduce_zero_and_single_element(codec_name):
    """All-zero payloads reduce to exact zeros (every codec's guarded
    scale decodes code 0 to 0.0) and the L=1 single-element cell works
    at every K — the degenerate shapes the lane padding must not
    disturb."""
    codec = get_codec(codec_name)
    for K in (1, 2, 8):
        parts = [codec.encode(jnp.zeros(17, jnp.float32))
                 for _ in range(K)]
        payload = jnp.stack([p for p, _ in parts])
        scales = jnp.stack([s for _, s in parts])
        out = DECODE[codec_name](payload, scales, 17)
        assert (np.asarray(out) == 0).all(), (
            f"{codec_name} K={K}: zero payload decoded to nonzero mean")
        payload, scales = _gathered(codec_name, K, 1, seed=K)
        want = _oracle(codec_name, 1, True)(payload, scales)
        got = DECODE[codec_name](payload, scales, 1)
        assert np.array_equal(np.asarray(want), np.asarray(got))


def test_codec_dispatch_uses_the_oracle_contract():
    """``decode_stacked_sum`` / ``decode_stacked_mean`` on the
    quantizing codecs match the kernels' oracle bitwise — the dispatch
    seam the drivers and fabrics call through."""
    for codec_name in sorted(DECODE):
        codec = get_codec(codec_name)
        payload, scales = _gathered(codec_name, 4, 333, seed=7)
        for mean in (True, False):
            via_codec = jax.jit(
                codec.decode_stacked_mean if mean
                else codec.decode_stacked_sum,
                static_argnames="length")((payload, scales), 333)
            want = _oracle(codec_name, 333, mean)(payload, scales)
            assert np.array_equal(np.asarray(want), np.asarray(via_codec))


@pytest.mark.parametrize("L", [1, 2, 7, 96, 128, 129, 1000])
def test_topk_select_bit_identical_to_oracle(L):
    """The fused top-k select returns the same values, indices and
    threshold as ``lax.top_k`` over the magnitudes, bitwise."""
    codec = get_codec("topk(r=0.125)")
    k = codec._k(L)
    rng = np.random.default_rng(L)
    dv = jnp.asarray(rng.standard_normal(L), jnp.float32)
    v_ref, i_ref, t_ref = jax.jit(codec.encode_ref)(dv)
    v_ker, i_ker, t_ker = topk_select(dv, k)
    assert np.array_equal(np.asarray(v_ref), np.asarray(v_ker))
    assert np.array_equal(np.asarray(i_ref), np.asarray(i_ker))
    assert float(t_ref) == float(t_ker)


@pytest.mark.parametrize("values", ["normal", "ties"])
def test_topk_select_bit_identical_across_blocks(values):
    """An update longer than one VMEM block (three grid steps): the
    running top k merged block by block equals ``lax.top_k`` over the
    whole vector, including ties whose entries sit in different blocks
    (the earlier block's index must win)."""
    L, k = 140_000, 64
    rng = np.random.default_rng(11)
    x = (rng.standard_normal(L) if values == "normal"
         else rng.integers(-3, 4, L))
    dv = jnp.asarray(x, jnp.float32)
    mags, idx = jax.lax.top_k(jnp.abs(dv), k)
    got_v, got_i, got_t = topk_select(dv, k)
    assert np.array_equal(np.asarray(jnp.take(dv, idx)), np.asarray(got_v))
    assert np.array_equal(np.asarray(idx), np.asarray(got_i))
    assert float(mags[k - 1]) == float(got_t)


def test_topk_select_breaks_ties_like_the_oracle():
    """Duplicate magnitudes (including +x vs -x) select the lowest
    index first — ``lax.top_k``'s stable order, which the kernel's
    first-occurrence argmax must reproduce."""
    dv = jnp.asarray([2.0, -2.0, 1.0, 2.0, -1.0, 1.0, 0.0, -2.0],
                     jnp.float32)
    for k in (1, 2, 3, 5, 8):
        mags, idx = jax.lax.top_k(jnp.abs(dv), k)
        want_v, want_i, want_t = jnp.take(dv, idx), idx, mags[k - 1]
        got_v, got_i, got_t = topk_select(dv, k)
        assert np.array_equal(np.asarray(want_v), np.asarray(got_v)), k
        assert np.array_equal(np.asarray(want_i), np.asarray(got_i)), k
        assert float(want_t) == float(got_t), k


def test_compressed_int4_trajectory_pinned_to_oracle_contract():
    """Driver-level regression: a ``compressed:int4`` CoCoA run's
    iterates are bit-identical to a run whose gather-side reduce is
    forced through the explicit sequential oracle — pinning that the
    driver's aggregate IS the decode+reduce contract (on TPU this
    compares the fused kernel against the oracle end-to-end; on CPU it
    pins the dispatch seam)."""
    from repro.core import CoCoAConfig, CoCoATrainer
    from repro.data import make_glm_data

    A, b, _ = make_glm_data(m=48, n=96, density=0.3, seed=3)

    def run_rounds(force_oracle: bool):
        cfg = CoCoAConfig(K=4, H=24, lam=1.0, eta=1.0, solver="scd_ref",
                          exchange="compressed:int4", seed=0)
        tr = CoCoATrainer(cfg, A, b)
        codec = tr.scheme.codec
        orig = type(codec).decode_stacked_sum
        if force_oracle:
            patched = (lambda self, parts, length:
                       self.decode_reduce_ref(parts, length, mean=False))
            type(codec).decode_stacked_sum = patched
        try:
            hist = tr.run(6, record_every=1)
        finally:
            type(codec).decode_stacked_sum = orig
        return hist

    h_dispatch = run_rounds(force_oracle=False)
    h_oracle = run_rounds(force_oracle=True)
    assert np.array_equal(np.asarray(h_dispatch.primal),
                          np.asarray(h_oracle.primal)), (
        "compressed:int4 trajectory drifted between the codec dispatch "
        "and the explicit sequential oracle")
    assert np.array_equal(np.asarray(h_dispatch.subopt),
                          np.asarray(h_oracle.subopt))
