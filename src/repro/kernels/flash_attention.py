"""Blockwise flash attention for TPU (Pallas), fwd, causal/full, GQA-aware.

TPU adaptation of FlashAttention (arXiv:2205.14135): the online-softmax
tiling is re-blocked for VMEM/MXU — (block_q x d) query tiles resident in
VMEM, KV streamed HBM->VMEM block by block along a *sequential* innermost
grid dimension, f32 running (m, l, acc) scratch carried across KV blocks
(grid-revisiting accumulation), and MXU-aligned tiles (multiples of 128 in
the lane dim, 8 in the sublane dim).  Causal blocks strictly above the
diagonal are skipped with ``pl.when`` — no wasted MXU work, unlike the
masked-full XLA fallback.

Layout: q (B, H, S, D); k/v (B, KH, T, D); GQA group G = H // KH is folded
by indexing the KV block map with ``h // G``.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_steps: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    run = True
    if causal:
        # skip blocks strictly above the diagonal
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, KH, T, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    _, KH, T, _ = k.shape
    G = H // KH
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    kv_steps = T // block_k
    grid = (B * H, S // block_q, kv_steps)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_steps=kv_steps)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, qi, ki: (bh // H, (bh % H) // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, qi, ki: (bh // H, (bh % H) // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------- #
# dispatch registration: "pallas" (native TPU) and "interpret" backends
# --------------------------------------------------------------------------- #
_PREF_Q = (512, 256, 128, 64, 32, 16, 8)
_PREF_K = (512, 256, 128, 64, 32, 16, 8)


def _block_cands(q, k, block_q, block_k):
    S, T = q.shape[2], k.shape[2]
    bqs = ([min(block_q, S)] if block_q
           else dispatch.block_candidates(S, _PREF_Q))
    bks = ([min(block_k, T)] if block_k
           else dispatch.block_candidates(T, _PREF_K))
    return bqs, bks


def _supports(q, k, v, *, causal=True, block_q=None, block_k=None):
    B, H, S, D = q.shape
    _, KH, T, _ = k.shape
    if H % KH != 0 or k.shape != v.shape:
        return False
    bqs, bks = _block_cands(q, k, block_q, block_k)
    return S % bqs[0] == 0 and T % bks[0] == 0


def _supports_native(q, k, v, *, causal=True, block_q=None, block_k=None):
    # Mosaic needs MXU-aligned score tiles: block_q on the sublane axis
    # (x8), block_k on the lane axis (x128).  Unaligned lengths (e.g. a
    # prime S, where the only valid block is S itself) must fall back to
    # the xla/ref backends instead of failing TPU compilation.
    if not _supports(q, k, v, causal=causal, block_q=block_q,
                     block_k=block_k):
        return False
    bqs, bks = _block_cands(q, k, block_q, block_k)
    return bqs[0] % 8 == 0 and bks[0] % 128 == 0


@functools.lru_cache(maxsize=None)
def _grad_ready(causal, block_q, block_k, interpret):
    """Kernel forward + chunked-XLA backward (fwd-only Pallas kernels are
    made differentiable by differentiating the reference at the inputs)."""
    from . import mha_xla
    kern = functools.partial(flash_attention, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    ref = functools.partial(mha_xla.flash_attention_xla, causal=causal)
    return dispatch.with_reference_vjp(kern, ref)


def _via_pallas(q, k, v, *, causal=True, block_q=None, block_k=None,
                interpret=False):
    bqs, bks = _block_cands(q, k, block_q, block_k)
    cands = [(bq, bk) for bq in bqs[:3] for bk in bks[:3]]
    bq, bk = dispatch.tuned_blocks(
        "flash_attention",
        (q.shape, k.shape, str(q.dtype), causal, interpret,
         block_q, block_k), cands,
        bench=lambda bq_, bk_: flash_attention(
            q, k, v, causal=causal, block_q=bq_, block_k=bk_,
            interpret=interpret),
        args=(q, k, v))
    return _grad_ready(causal, bq, bk, interpret)(q, k, v)


dispatch.register("flash_attention", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_native, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("flash_attention", "interpret",
                  priority=20, supports=_supports, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
