"""The few jax spellings the codebase shares.

Everything that builds meshes or shard_maps, and every Pallas kernel
entry point, goes through here, so the platform switch and the mesh
conventions live in one file. The code targets the installed jax
(``jax>=0.9``): ``jax.shard_map``, ``Mesh`` axis types.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether the default backend is a real TPU — the one shared
    switch for every Pallas kernel entry point (``kernels/ops.py``,
    ``kernels/quant.py``) and for codec dispatch."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel wrapper's ``interpret=None`` default: compiled
    on TPU, interpret-mode emulation everywhere else."""
    return not on_tpu() if interpret is None else interpret


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
