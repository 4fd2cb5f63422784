"""`ServeConfig`: one consolidated, validated knob surface for the
serve engine.

The engine grew its knobs one at a time — slot pool, paging, chunked
prefill, and now prefix caching — and every layer above it (the launch
driver, the tests) re-spelled the same widening bare-kwarg list.
``ServeConfig`` freezes that surface into a single dataclass consumed by
:class:`repro.serve.ServeEngine` and ``repro.launch.serve``; the old
bare kwargs keep working for one release via a mapping shim on the
engine that emits ``DeprecationWarning``.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Any

import jax.numpy as jnp

from .paging import PrefixCache
from .scheduler import SlotScheduler


@dataclass(frozen=True)
class ServeConfig:
    """Engine-level serving configuration.

    * ``max_batch`` — cache slot pool size (max in-flight requests).
    * ``max_len`` — per-request cache row budget (prompt + generation).
    * ``policy`` — admission: ``"continuous"`` (admit into any free slot
      mid-decode) or ``"static"`` (lockstep batches, the oracle).
    * ``kv_block_size`` — tokens per paged-KV block; 0 / None keeps the
      dense per-slot ``max_len`` rows (the pre-paging layout).
    * ``kv_pool_blocks`` — usable blocks in the paged pool (None =
      dense-equivalent capacity ``max_batch * ceil(max_len/block)``).
    * ``prefill_chunk_tokens`` — per-step prompt-token budget of the
      mixed step (None = auto: two KV blocks under paging, 256 dense;
      0 = stall-the-world prefill, the A/B oracle).
    * ``q_chunk`` — prefill attention query-chunk size.
    * ``kernel_backend`` — force a kernel dispatch backend
      (pallas | interpret | xla | ref); None = auto.
    * ``dtype`` — cache / activation dtype.
    * ``prefix_cache`` — share identical whole prompt blocks between
      requests via the refcounted copy-on-write prefix index
      (:class:`repro.serve.PrefixCache`).  Effective only where it is
      sound: paged cache, chunked prefill, and an attention-only arch
      (recurrent state cannot skip prompt tokens); elsewhere it is
      silently inert.  False disables sharing outright — the oracle the
      prefix tests diff against.
    * ``prefix_evict`` — prefix-index retention: ``"lru"`` keeps
      published blocks warm after their users retire (leaf-first LRU
      eviction when the pool runs dry), ``"none"`` shares only between
      concurrently live requests.
    * ``kv_quant`` — paged-pool block quantization: ``"int8"`` stores KV
      blocks as int8 with per-token-slot per-head f32 scales riding the
      block table (quantize on write, dequantize after the block gather
      in every attention backend); None / ``"none"`` keeps the fp pool.
      Requires the paged cache (``kv_block_size > 0``); like
      ``prefix_cache`` it is silently inert for attention-free archs.
    """

    max_batch: int
    max_len: int
    policy: str = "continuous"
    kv_block_size: int | None = 128
    kv_pool_blocks: int | None = None
    prefill_chunk_tokens: int | None = None
    q_chunk: int = 256
    kernel_backend: str | None = None
    dtype: Any = field(default=jnp.float32, repr=False)
    prefix_cache: bool = True
    prefix_evict: str = "lru"
    kv_quant: str | None = None

    KV_QUANT = (None, "none", "int8")

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.policy not in SlotScheduler.POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; expected "
                             f"one of {SlotScheduler.POLICIES}")
        if self.prefix_evict not in PrefixCache.EVICTION:
            raise ValueError(
                f"unknown prefix_evict {self.prefix_evict!r}; expected "
                f"one of {PrefixCache.EVICTION}")
        if self.kv_block_size and self.kv_block_size < 0:
            raise ValueError(f"kv_block_size must be >= 0, "
                             f"got {self.kv_block_size}")
        if self.kv_quant not in self.KV_QUANT:
            raise ValueError(f"unknown kv_quant {self.kv_quant!r}; "
                             f"expected one of {self.KV_QUANT}")
        if (self.kv_quant not in (None, "none")
                and not self.kv_block_size):
            raise ValueError(
                "kv_quant requires the paged KV cache (kv_block_size > 0); "
                "the dense per-slot rows are always fp")

    def replace(self, **changes) -> "ServeConfig":
        from dataclasses import replace
        return replace(self, **changes)


#: the bare ServeEngine kwargs the one-release deprecation shim accepts
#: (everything ServeConfig carries)
LEGACY_KWARGS = tuple(f.name for f in fields(ServeConfig))
