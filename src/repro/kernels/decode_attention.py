"""Flash-decode (split-KV) attention kernel for TPU (Pallas).

Single-token decode against a long KV cache (the ``decode_32k`` /
``long_500k`` serving shapes).  TPU adaptation of FlashDecoding
(arXiv:2311.01282): the KV cache is streamed in blocks along a sequential
grid dimension with f32 (m, l, acc) running statistics in VMEM scratch.
The GQA group dimension G becomes the *sublane* axis of the q tile —
(G x D) @ (D x block_k) keeps the MXU busy even at q_len == 1, which a
naive (1 x D) layout cannot.

Layout: q (B, KH, G, D); k/v (B, KH, T, D); kv_len masks valid positions —
a scalar (every row at the same position) or a (B,) vector (per-slot
positions, the continuous-batching serve engine's ragged decode: each
cache slot carries its own request at its own depth).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

from . import dispatch

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, block_k: int, kv_steps: int, scale: float,
                   kv_heads: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0) // kv_heads]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, bk)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len, *, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, D); k/v: (B, KH, T, D); kv_len: scalar int32 or a
    (B,) vector of per-slot valid lengths.  Returns (B, KH, G, D)."""
    from .ref import normalize_kv_len

    B, KH, G, D = q.shape
    T = k.shape[2]
    block_k = min(block_k, T)
    assert T % block_k == 0, (T, block_k)
    kv_steps = T // block_k
    grid = (B * KH, kv_steps)
    scale = 1.0 / math.sqrt(D)
    kv_len = normalize_kv_len(kv_len, B)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               kv_steps=kv_steps, scale=scale, kv_heads=KH)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, D), lambda bk, ki: (bk // KH, bk % KH, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bk, ki: (bk // KH, bk % KH, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bk, ki: (bk // KH, bk % KH, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda bk, ki: (bk // KH, bk % KH, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # m
            pltpu.VMEM((G, 1), jnp.float32),   # l
            pltpu.VMEM((G, D), jnp.float32),   # acc
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len, q, k, v)


# --------------------------------------------------------------------------- #
# dispatch registration: "pallas" (native TPU) and "interpret" backends
# --------------------------------------------------------------------------- #
_PREF_K = (1024, 512, 256, 128, 64, 32, 16, 8)


def _block(k, block_k) -> int:
    """The caller's block, capped at the cache length, else the largest
    preferred block dividing it."""
    T = k.shape[2]
    return (min(block_k, T) if block_k
            else dispatch.block_candidates(T, _PREF_K)[0])


def _supports(q, k, v, kv_len, *, block_k=None):
    # mixed-step 5-d q (per-slot variable query tokens) falls back to the
    # ref/xla backends — this kernel is single-token-per-slot only
    if q.ndim != 4 or k.shape != v.shape or q.shape[1] != k.shape[1]:
        return False
    return k.shape[2] % _block(k, block_k) == 0


def _supports_native(q, k, v, kv_len, *, block_k=None):
    # Mosaic needs the (G, block_k) score tile lane axis 128-aligned;
    # unaligned cache lengths fall back to the ref backend.
    return (_supports(q, k, v, kv_len, block_k=block_k)
            and _block(k, block_k) % 128 == 0)


def _via_pallas(q, k, v, kv_len, *, block_k=None, interpret=False):
    return decode_attention(q, k, v, kv_len, block_k=_block(k, block_k),
                            interpret=interpret)


dispatch.register("decode_attention", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_native, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("decode_attention", "interpret",
                  priority=20, supports=_supports, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
