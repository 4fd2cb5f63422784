"""Paged flash-decode attention kernel for TPU (Pallas).

Single-token decode against a *paged* KV cache: instead of one dense
``(B, max_len, KH, D)`` row per slot, K/V live in a global pool of
fixed-size blocks ``(num_blocks, block_size, KH, D)`` and each slot owns
a **block table** — a ``(B, pages)`` int32 map from logical page index
to physical pool block (vLLM's PagedAttention, arXiv:2309.06180,
adapted to the TPU flash-decode layout of ``decode_attention``).

The block table and per-slot valid lengths ride in as *scalar-prefetch*
operands (``pltpu.PrefetchScalarGridSpec``): the k/v BlockSpec index
maps dereference ``table[b, page]`` before the kernel body runs, so each
grid step DMAs exactly one physical block — the kernel never sees (and
HBM never stores) the dense ``max_len`` view.  Pages at or beyond a
slot's ``kv_len`` are skipped for compute and their table entries point
at physical block 0 (the engine's trash block), keeping the prefetched
DMA harmless.  As in ``decode_attention``, the GQA group dimension G is
the sublane axis of the q tile so the MXU stays busy at q_len == 1, with
f32 (m, l, acc) running statistics in VMEM scratch.
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


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, pages: int, scale: float, kv_heads: int,
                  quant: bool):
    """One grid step = one slot's page: the whole physical block, every
    KV head, arrives in VMEM; heads are stepped in a static loop (the
    per-head K/V rows are a strided load out of the block tile).  With
    ``quant`` the block is int8 and ``ks_ref``/``vs_ref`` hold its
    (block_size, KH) f32 scale rows, applied in VMEM right before the
    dots."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[pl.program_id(0)]
    start = pi * block_size

    @pl.when(start < kv_len)
    def _compute():
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32)             # (G, D)
            k = k_ref[0, :, h, :].astype(jnp.float32)       # (bs, D)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quant:
                k = k * ks_ref[0, :, h:h + 1]               # (bs, 1) scales
                v = v * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (G, bs)
            kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < kv_len, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(pi == pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           kv_len, *, k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, D); k_pool/v_pool: (NB, bs, KH, D); block_tables:
    (B, pages) int32; kv_len: scalar int32 or a (B,) vector of per-slot
    valid lengths.  Returns (B, KH, G, D).

    With ``k_scale``/``v_scale`` ((NB, bs, KH) f32) the pools are int8;
    each grid step DMAs the physical block's scale rows alongside the
    payload (same scalar-prefetched table dereference) and dequantizes in
    VMEM."""
    from .ref import normalize_kv_len

    B, KH, G, D = q.shape
    _, bs, _, _ = k_pool.shape
    pages = block_tables.shape[1]
    scale = 1.0 / math.sqrt(D)
    kv_len = normalize_kv_len(kv_len, B)
    block_tables = block_tables.astype(jnp.int32)
    quant = k_scale is not None

    # whole physical blocks (every KV head): the trailing (KH, D) block
    # dims equal the pool's, which Mosaic's tiling rule accepts for any KH
    pool_spec = pl.BlockSpec((1, bs, KH, D),
                             lambda b, pi, bt, ln: (bt[b, pi], 0, 0, 0))
    slot_spec = pl.BlockSpec((1, KH, G, D),
                             lambda b, pi, bt, ln: (b, 0, 0, 0))
    in_specs = [slot_spec, pool_spec, pool_spec]
    operands = [q, k_pool, v_pool]
    if quant:
        scale_spec = pl.BlockSpec((1, bs, KH),
                                  lambda b, pi, bt, ln: (bt[b, pi], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    kernel = functools.partial(_paged_kernel, block_size=bs, pages=pages,
                               scale=scale, kv_heads=KH, quant=quant)
    # Scalar prefetch: the block table (and lengths) are available to the
    # index maps, so the pool blockspec fetches table[b, page] directly.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pages),
        in_specs=in_specs,
        out_specs=slot_spec,
        scratch_shapes=[
            pltpu.VMEM((KH, G, 1), jnp.float32),   # m
            pltpu.VMEM((KH, G, 1), jnp.float32),   # l
            pltpu.VMEM((KH, G, D), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables, kv_len, *operands)


# --------------------------------------------------------------------------- #
# dispatch registration: "pallas" (native TPU) and "interpret" backends
# --------------------------------------------------------------------------- #
def _supports(q, k_pool, v_pool, block_tables, kv_len, *,
              k_scale=None, v_scale=None):
    # mixed-step 5-d q (per-slot variable query tokens) falls back to the
    # ref/xla gather backends — this kernel is single-token-per-slot only
    if (k_scale is None) != (v_scale is None):
        return False
    if k_scale is not None and k_scale.shape != k_pool.shape[:-1]:
        return False
    return (q.ndim == 4
            and k_pool.shape == v_pool.shape
            and q.shape[1] == k_pool.shape[2]
            and block_tables.ndim == 2
            and block_tables.shape[0] == q.shape[0])


def _supports_native(q, k_pool, v_pool, block_tables, kv_len, *,
                     k_scale=None, v_scale=None):
    # Mosaic wants the (G, block_size) score tile lane axis 128-aligned;
    # pools with a smaller block size fall back to the gather backend.
    return _supports(q, k_pool, v_pool, block_tables, kv_len,
                     k_scale=k_scale, v_scale=v_scale) \
        and k_pool.shape[1] % 128 == 0


def _via_pallas(q, k_pool, v_pool, block_tables, kv_len, *,
                k_scale=None, v_scale=None, interpret=False):
    return paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len,
                                  k_scale=k_scale, v_scale=v_scale,
                                  interpret=interpret)


dispatch.register("paged_decode_attention", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_native, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("paged_decode_attention", "interpret",
                  priority=20, supports=_supports, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
