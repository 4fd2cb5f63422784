"""Backend-portable kernel dispatch.

Every compute hot-spot ("op") registers multiple implementations — native
Pallas-TPU, Pallas-interpret, chunked-XLA, pure-jnp reference — and call
sites ask the *registry* for the op instead of importing a kernel module.
Selection is by platform / dtype / shape via per-impl ``supports``
predicates and priorities, so:

* a JAX rename breaks one adapter, not every consumer;
* CPU-only hosts transparently get the reference/XLA path (Pallas TPU
  kernels cannot lower to the CPU backend);
* TPU hosts get the native kernel, whose block sizes each kernel module
  takes from its own preference list (:func:`block_candidates`).

Overrides, strongest first:
  1. ``backend=`` argument to :func:`call`;
  2. the :func:`force_backend` context (used by train/serve drivers);
  3. ``REPRO_KERNEL_BACKEND_<OP>`` env var (op name upper-cased);
  4. ``REPRO_KERNEL_BACKEND`` env var;
  5. automatic selection (highest-priority impl whose platform matches and
     whose ``supports`` predicate accepts the arguments; under a mesh,
     an impl that is not ``spmd_safe`` only where its ``local_specs``
     maps the call, see below).  ``interpret`` (the Pallas interpreter)
     is a test harness: it runs only when asked for by name, never as an
     automatic choice.

Under a multi-device mesh an impl that is not ``spmd_safe`` (an opaque
custom call) runs only where the call passes ``specs=``, the
PartitionSpecs its array arguments are sharded by, and the impl's
``local_specs`` rule finds a split it can run shard by shard: the call
is then wrapped in ``shard_map``, so every device runs the kernel on its
own slice, and every gate is checked on the local shapes.  Elsewhere it
is passed over as in step 5.

Every resolution is counted per (op, backend) — :func:`selection_counts`
— every one run shard by shard also in :func:`spmd_maps`, and every
spmd-unsafe impl passed over under a mesh in :func:`spmd_skips`, so a
run can show which kernels it actually executed.  Selection happens at
trace time: the counts are traces, not calls.

Ops registered by the sibling modules (canonical layouts/signatures):
  flash_attention(q, k, v, *, causal, block_q, block_k)
      q: (B, H, S, D); k/v: (B, KH, T, D) -> (B, H, S, D)
  decode_attention(q, k, v, kv_len, *, block_k)
      q: (B, KH, G, D); k/v: (B, KH, T, D) -> (B, KH, G, D)
      kv_len: scalar or (B,) per-slot valid lengths (continuous batching)
  paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len)
      q: (B, KH, G, D); k_pool/v_pool: (NB, block_size, KH, D);
      block_tables: (B, pages) int32 page->physical-block map
      -> (B, KH, G, D)  (the serve engine's paged KV cache)
  wkv6(r, k, v, w, u, *, chunk, initial_state, return_state)
      r/k/v/w: (B, H, T, N); u: (H, N) -> (B, H, T, N) [, (B, H, N, N)]
  mamba_scan(dt, B, C, x, A, D, *, chunk, initial_state, return_state)
      dt/x: (B, S, di); B/C: (B, S, N); A: (di, N); D: (di,)
      -> (B, S, di) [, (B, di, N) f32]
  moe_dispatch_combine(x, gate_vals, expert_idx, wi, wg, wo, *,
                       capacity, constrain)
      x: (B, S, D); gate_vals: (B, S, K); expert_idx: (B, S, K) int32;
      wi/wg: (E, D, F); wo: (E, F, D) -> (B, S, D)
"""

from __future__ import annotations

import contextlib
import functools
import logging
import math
import os
import threading
from collections import Counter
from dataclasses import dataclass
from typing import Any, Callable

import jax

from repro import compat
from repro.core.sharding import current_mesh, entry_axes

log = logging.getLogger(__name__)

ENV_GLOBAL = "REPRO_KERNEL_BACKEND"


@dataclass(frozen=True)
class Impl:
    op: str
    backend: str                      # "pallas" | "interpret" | "xla" | "ref"
    fn: Callable[..., Any]
    platforms: tuple[str, ...] = ("*",)   # eligible jax backends; "*" = any
    priority: int = 0                     # higher wins among eligible
    supports: Callable[..., bool] | None = None  # hard capability gate
    # auto_gate is a *preference*, not a capability: consulted only
    # during automatic selection (e.g. "reference path only below this
    # size").  An explicit backend= / env override bypasses it.
    auto_gate: Callable[..., bool] | None = None
    # False for impls that lower to an opaque custom call (pallas_call)
    # with no SPMD partitioning rule: under a multi-device mesh GSPMD
    # would replicate their operands (all-gathering full q/k/v).  There
    # such an impl runs shard-mapped where ``local_specs`` accepts the
    # call's specs; otherwise auto-selection skips it, and an explicit
    # backend= still wins, run unmapped.
    spmd_safe: bool = True
    # local_specs(sizes, specs, *args, **kwargs) -> (in_specs, out_specs)
    # or None: ``sizes`` maps mesh axis -> devices, ``specs`` are the
    # PartitionSpecs of the positional arrays.  A result means each
    # device computes its own output shard from its input shards alone.
    local_specs: Callable[..., Any] | None = None

    def eligible(self, platform: str, args, kwargs, *,
                 auto: bool = True) -> bool:
        if "*" not in self.platforms and platform not in self.platforms:
            return False
        gates = [self.supports] + ([self.auto_gate] if auto else [])
        for gate in gates:
            if gate is None:
                continue
            try:
                if not gate(*args, **kwargs):
                    return False
            except Exception:  # a predicate must never take the process down
                log.exception("predicate failed for %s/%s",
                              self.op, self.backend)
                return False
        return True


_REGISTRY: dict[str, dict[str, Impl]] = {}
_forced = threading.local()
_registered_builtins = False


def register(op: str, backend: str, *, platforms: tuple[str, ...] = ("*",),
             priority: int = 0, supports: Callable[..., bool] | None = None,
             auto_gate: Callable[..., bool] | None = None,
             spmd_safe: bool = True,
             local_specs: Callable[..., Any] | None = None):
    """Decorator: register ``fn`` as the ``backend`` implementation of
    ``op``.  Re-registration replaces (module reloads)."""

    def deco(fn):
        _REGISTRY.setdefault(op, {})[backend] = Impl(
            op=op, backend=backend, fn=fn, platforms=tuple(platforms),
            priority=priority, supports=supports, auto_gate=auto_gate,
            spmd_safe=spmd_safe, local_specs=local_specs)
        return fn

    return deco


def _ensure_builtins() -> None:
    """Import the sibling kernel modules so their registrations run.
    Lazy (first call) to avoid import cycles with consumers."""
    global _registered_builtins
    if _registered_builtins:
        return
    _registered_builtins = True
    from . import ref  # noqa: F401  pure-jnp reference backends
    from . import mha_xla  # noqa: F401  chunked-XLA attention backend
    from . import mamba_scan  # noqa: F401  selective-scan backends
    from . import moe_kernels  # noqa: F401  MoE dispatch/combine backends
    from . import decode_attention  # noqa: F401
    from . import flash_attention  # noqa: F401
    from . import paged_decode_attention  # noqa: F401
    from . import rwkv6_scan  # noqa: F401


def backends(op: str) -> dict[str, Impl]:
    _ensure_builtins()
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[op]


@contextlib.contextmanager
def force_backend(backend: str | None):
    """Force every :func:`call` in this thread to ``backend`` (None =
    no-op).  Selection happens at trace time, so wrapping a ``jax.jit``
    *call* (or the first trace) is sufficient."""
    prev = getattr(_forced, "backend", None)
    _forced.backend = backend
    try:
        yield
    finally:
        _forced.backend = prev


def _mesh_active() -> bool:
    """True when a multi-device mesh is active (``use_mesh``): SPMD
    partitioning is in play and spmd-unsafe impls run only shard-mapped."""
    mesh = current_mesh()
    return mesh is not None and mesh.devices.size > 1


@dataclass(frozen=True)
class _Local:
    """How a call runs shard by shard: the specs for ``shard_map`` and
    the per-device arguments the gates are checked on."""
    in_specs: tuple
    out_specs: Any
    args: tuple


def _local(impl: Impl, specs, args, kwargs) -> _Local | None:
    """The shard-by-shard form of this call, if ``impl`` has one for the
    given ``specs`` on the active mesh."""
    mesh = current_mesh()
    if impl.local_specs is None or specs is None or mesh is None:
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    mapped = impl.local_specs(sizes, tuple(specs), *args, **kwargs)
    if mapped is None:
        return None
    in_specs, out_specs = mapped
    local = []
    for x, spec in zip(args, in_specs):
        degs = [math.prod(sizes[a] for a in entry_axes(e)) for e in spec]
        degs += [1] * (x.ndim - len(degs))
        if any(n % d for n, d in zip(x.shape, degs)):
            return None
        local.append(jax.ShapeDtypeStruct(
            tuple(n // d for n, d in zip(x.shape, degs)), x.dtype))
    return _Local(tuple(in_specs), out_specs, tuple(local))


def _override_for(op: str) -> str | None:
    forced = getattr(_forced, "backend", None)
    if forced:
        return forced
    return (os.environ.get(f"{ENV_GLOBAL}_{op.upper()}")
            or os.environ.get(ENV_GLOBAL) or None)


def select(op: str, *args, backend: str | None = None, specs=None,
           **kwargs) -> Impl:
    """Resolve the implementation that :func:`call` would run.

    An explicit ``backend=`` is strict: ineligible -> ValueError.  A
    force_backend-context / env-var override is a *preference*: an
    unknown name still raises (typos must be loud), but a known backend
    that cannot handle this particular call (e.g. the stateless Pallas
    wkv6 asked for the stateful decode form) logs a warning and falls
    through to auto-selection, so one override can steer a whole model
    without crashing the ops it cannot cover.  ``specs`` are the
    PartitionSpecs of the positional arrays under the active mesh (see
    the module docstring).
    """
    return _resolve(op, args, kwargs, backend, specs)[0]


def _resolve(op: str, args, kwargs, backend: str | None,
             specs) -> tuple[Impl, _Local | None]:
    impls = backends(op)
    platform = compat.default_platform()
    strict = backend is not None
    backend = backend or _override_for(op)
    spmd = _mesh_active()
    if backend is not None:
        if backend not in impls:
            raise ValueError(
                f"backend {backend!r} not registered for op {op!r} "
                f"(have: {sorted(impls)})")
        impl = impls[backend]
        local = (_local(impl, specs, args, kwargs)
                 if spmd and not impl.spmd_safe else None)
        if local is not None and impl.eligible(platform, local.args, kwargs,
                                               auto=False):
            return impl, local
        if impl.eligible(platform, args, kwargs, auto=False):
            return impl, None
        if strict:
            raise ValueError(
                f"backend {backend!r} for op {op!r} does not support "
                f"platform={platform!r} with the given shapes/dtypes")
        log.warning("forced backend %r cannot handle this %r call; "
                    "auto-selecting", backend, op)
    ranked = sorted(impls.values(), key=lambda i: -i.priority)
    for impl in ranked:
        if impl.backend == "interpret":
            continue
        if spmd and not impl.spmd_safe:
            local = _local(impl, specs, args, kwargs)
            if local is not None and impl.eligible(platform, local.args,
                                                   kwargs):
                return impl, local
            if impl.eligible(platform, args, kwargs):
                with _COUNT_LOCK:
                    _SPMD_SKIPS[(op, impl.backend)] += 1
            continue
        if impl.eligible(platform, args, kwargs):
            return impl, None
    raise RuntimeError(
        f"no eligible backend for op {op!r} on platform {platform!r}; "
        f"registered: {sorted(impls)}")


_COUNT_LOCK = threading.Lock()
_SELECTIONS: Counter = Counter()       # (op, backend) -> resolutions
_SPMD_SKIPS: Counter = Counter()       # (op, backend) -> passed over
_SPMD_MAPS: Counter = Counter()        # (op, backend) -> run shard-mapped


def call(op: str, *args, backend: str | None = None, specs=None,
         **kwargs):
    """Dispatch ``op`` to the selected backend implementation; ``specs``
    as in :func:`select`."""
    impl, local = _resolve(op, args, kwargs, backend, specs)
    record_selection(op, impl.backend)
    if local is None:
        return impl.fn(*args, **kwargs)
    with _COUNT_LOCK:
        _SPMD_MAPS[(op, impl.backend)] += 1
    return compat.shard_map(
        functools.partial(impl.fn, **kwargs), mesh=current_mesh(),
        in_specs=local.in_specs, out_specs=local.out_specs)(*args)


def record_selection(op: str, backend: str) -> None:
    """Count a resolution made outside :func:`call` (a kernel's backward
    choosing its own path at trace time), so that
    :func:`selection_counts` shows it beside the dispatched ones."""
    with _COUNT_LOCK:
        _SELECTIONS[(op, backend)] += 1


def selection_counts() -> dict[tuple[str, str], int]:
    """How often :func:`call` resolved each (op, backend) since the last
    :func:`reset_selection_counts` (once per trace under ``jit``)."""
    with _COUNT_LOCK:
        return dict(_SELECTIONS)


def spmd_skips() -> dict[tuple[str, str], int]:
    """(op, backend) pairs that were eligible but passed over because a
    multi-device mesh was active and the impl is not ``spmd_safe``."""
    with _COUNT_LOCK:
        return dict(_SPMD_SKIPS)


def spmd_maps() -> dict[tuple[str, str], int]:
    """(op, backend) pairs that ran shard-mapped under a multi-device
    mesh (each also counts in :func:`selection_counts`)."""
    with _COUNT_LOCK:
        return dict(_SPMD_MAPS)


def reset_selection_counts() -> None:
    with _COUNT_LOCK:
        _SELECTIONS.clear()
        _SPMD_SKIPS.clear()
        _SPMD_MAPS.clear()


def block_candidates(dim: int, preferred: tuple[int, ...]) -> list[int]:
    """Block sizes (largest first) from ``preferred`` that evenly divide
    ``dim``; always non-empty (``dim`` itself divides)."""
    cands = [b for b in sorted(set(preferred), reverse=True)
             if b <= dim and dim % b == 0]
    return cands or [dim]


def with_reference_vjp(fn: Callable, ref_fn: Callable) -> Callable:
    """Make a forward-only kernel differentiable: forward runs ``fn``,
    backward differentiates ``ref_fn`` (the mathematically identical
    reference) at the saved inputs.  Standard treatment for fwd-only
    Pallas kernels — the bwd pass re-runs in XLA, which is memory-safe
    and works on every platform."""

    @jax.custom_vjp
    def wrapped(*args):
        return fn(*args)

    def fwd(*args):
        return fn(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(ref_fn, *args)
        return vjp(g)

    wrapped.defvjp(fwd, bwd)
    return wrapped
