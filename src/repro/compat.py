"""The few JAX symbols the tree spells in one place.

The repo targets the installed JAX (0.9.0).  These helpers exist so that
a future upstream rename is a one-line fix here instead of a tree-wide
one: no module outside this one references ``CompilerParams`` or
``AxisType`` (the CI lint job greps for it).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
from jax.experimental.pallas import tpu as _pltpu

__all__ = [
    "tpu_compiler_params",
    "make_mesh",
    "default_platform",
    "shard_map",
]


def tpu_compiler_params(*, dimension_semantics: Sequence[str], **kw) -> Any:
    """The Mosaic compiler-params object for a ``pallas_call``."""
    return _pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics), **kw)


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """``jax.make_mesh`` with every axis in auto (GSPMD) sharding mode —
    the repo shards through ``with_sharding_constraint``, not explicit
    sharding types."""
    auto = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=auto, devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off (the profiling
    microbench maps raw collectives whose replication XLA cannot always
    infer)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=None)
def default_platform() -> str:
    """'cpu' | 'gpu' | 'tpu' for the default JAX backend."""
    return jax.default_backend()
