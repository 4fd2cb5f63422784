"""Realize :class:`LayerConfig` as JAX shardings.

A searched strategy is *realized* by mapping each layer's config onto
``PartitionSpec``s for its activations and parameters, then constraining the
tensors inside the jitted step (``jax.lax.with_sharding_constraint``).  XLA's
SPMD partitioner inserts exactly the collectives the cost model priced.

The active device mesh is threaded through a context variable so model code
stays mesh-agnostic (a no-op on a single device — smoke tests see no mesh).
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import LayerConfig

_state = threading.local()


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        yield mesh
    finally:
        _state.mesh = prev


# --------------------------------------------------------------------------- #
DimName = str | None


def pspec(cfg: LayerConfig, dims: Sequence[DimName]) -> P:
    """PartitionSpec for an array whose axes carry logical dims ``dims``.

    ``None`` entries (and dims the config does not shard) are unsharded.
    ``dims`` may name any logical dim — e.g. ``("batch", "seq", "heads",
    None)`` for a (B, S, H, Dh) activation.
    """
    entries = []
    for d in dims:
        axes = cfg.axes_for(d) if d is not None else ()
        if len(axes) == 0:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(tuple(axes))
    # trailing Nones can be dropped but keeping them is harmless
    return P(*entries)


def sharding(cfg: LayerConfig, dims: Sequence[DimName],
             mesh: Mesh | None = None) -> NamedSharding | None:
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return None
    spec = pspec(cfg, dims)
    # drop axes not present in this mesh (e.g. "pod" on a single-pod mesh)
    cleaned = []
    for entry in spec:
        if entry is None:
            cleaned.append(None)
        elif isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            cleaned.append(kept if kept else None)
        else:
            cleaned.append(entry if entry in mesh.axis_names else None)
    return NamedSharding(mesh, P(*cleaned))


def entry_axes(entry) -> tuple[str, ...]:
    """The mesh axes one PartitionSpec entry names, major first."""
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def fitted_pspec(shape: Sequence[int], cfg: LayerConfig,
                 dims: Sequence[DimName], mesh: Mesh | None = None
                 ) -> P | None:
    """The spec :func:`constrain` applies to an array of ``shape`` under
    ``mesh`` (default: the active one; None without a mesh).

    Entries whose shard count does not divide the array dim lose axes,
    left-first, until it does (e.g. 8 KV heads on a 16-way model axis ->
    replicated KV, the standard GQA-TP fallback), so every shard is the
    same size.
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = []
    for dim_size, entry in zip(shape, sharding(cfg, dims, mesh).spec):
        axes = entry_axes(entry)
        while axes and dim_size % math.prod(sizes[a] for a in axes):
            axes = axes[1:]
        entries.append(None if not axes
                       else axes if len(axes) > 1 else axes[0])
    return P(*entries)


def constrain(x: jax.Array, cfg: LayerConfig,
              dims: Sequence[DimName]) -> jax.Array:
    """``with_sharding_constraint`` to :func:`fitted_pspec` under the
    active mesh (no-op without)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, fitted_pspec(x.shape, cfg, dims, mesh)))


def constrain_tree(tree, cfg: LayerConfig, dims_tree) -> object:
    """Constrain a pytree: ``dims_tree`` mirrors ``tree`` with dim tuples."""
    return jax.tree.map(
        lambda x, d: constrain(x, cfg, d), tree, dims_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            isinstance(e, (str, type(None))) for e in t))
