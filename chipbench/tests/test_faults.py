"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run.run`` on the repository's cells at 1,024 x 64
(the look for a chip is skipped: the CPU stands in), with one fault
planted in the program, and sees ``correct`` false:

  * a step that returns its state unchanged: the local solver returns
    alpha as it was and a zero update;
  * half of the batch left out, the mean taken over the rest: the
    exchange sums the first half of the workers' updates and scales by
    two, while every worker keeps its own alpha;
  * the exchange between chips left out (the four-chip cell): each
    chip applies only its own update;
  * an answer altered where it is produced: the local solver's update
    is off by 1%.

The sound run of each cell is the control that comes out correct.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from conftest import cpu_devices

from chipbench import run

CELLS = ["tiny.h-local", "tinyx4.h-local", "tiny.h16"]


def _run(root, workload, seed=11):
    return run.run(workload, seed, 0.3, False, root=root,
                   devices_for=cpu_devices)


def _wrap_solver(monkeypatch, fault):
    from repro.kernels import ops

    real = ops.scd_steps_kernel

    def broken(A_k, col_sq, alpha_k, w, idx, **kw):
        dv, alpha_new = real(A_k, col_sq, alpha_k, w, idx, **kw)
        return fault(dv, alpha_new, alpha_k)

    monkeypatch.setattr(ops, "scd_steps_kernel", broken)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    r = _run(tiny_root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_state_unchanged(tiny_root, monkeypatch, workload):
    _wrap_solver(monkeypatch, lambda dv, a_new, a_old: (0 * dv, a_old))
    r = _run(tiny_root, workload)
    assert not r["correct"]
    assert r["checks"]["failed_solves"]["value"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered(tiny_root, monkeypatch, workload):
    _wrap_solver(monkeypatch, lambda dv, a_new, a_old: (1.01 * dv, a_new))
    r = _run(tiny_root, workload)
    assert not r["correct"]
    gap = r["checks"]["primal_gap"]
    assert gap["value"] is None or gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", ["tiny.h-local", "tiny.h16"])
def test_half_the_workers_left_out(tiny_root, monkeypatch, workload):
    from repro.core.distributed import CommScheme

    def half(self, updates, state=None):
        k = updates.shape[0] // 2
        return 2.0 * jnp.sum(updates[:k], axis=0)

    monkeypatch.setattr(CommScheme, "all_reduce_stacked", half)
    assert not _run(tiny_root, workload)["correct"]


def test_half_the_chips_left_out(tiny_root, monkeypatch):
    from jax import lax

    from repro.core.distributed import CommScheme

    def half(self, update, axis, backend=None, state=None):
        keep = lax.axis_index(axis) < lax.psum(1, axis) // 2
        return 2.0 * lax.psum(jnp.where(keep, update, 0.0), axis)

    monkeypatch.setattr(CommScheme, "all_reduce", half)
    assert not _run(tiny_root, "tinyx4.h-local")["correct"]


def test_exchange_between_chips_left_out(tiny_root, monkeypatch):
    from repro.core.distributed import CommScheme

    monkeypatch.setattr(CommScheme, "all_reduce",
                        lambda self, update, axis, backend=None,
                        state=None: update)
    assert not _run(tiny_root, "tinyx4.h-local")["correct"]
