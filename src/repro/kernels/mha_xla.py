"""Chunked online-softmax attention in pure XLA (the "xla" backend).

This is the generic, memory-safe attention implementation: peak memory is
O(q_chunk * kv_chunk) per (B, H) instead of O(S * T).  It lowers on every
JAX platform, is differentiable, and supports arbitrary query/KV position
vectors — so it backs three roles:

* the ``flash_attention`` dispatch backend wherever Pallas cannot run (or
  the reference path would materialize too large a score tensor);
* the backward pass of the Pallas flash kernel for shapes its own
  backward kernels cannot block (reference VJP);
* the ``kv_override`` / cross-attention path in ``repro.models.layers``
  (which needs free-form positions the blocked kernels do not take).

Historically this lived in ``repro.models.layers._mha_core``; it moved
here so every attention implementation registers through
``repro.kernels.dispatch``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import dispatch

NEG_INF = -1e30


def mha_chunked(q, k, v, *, causal: bool, q_positions, kv_positions,
                q_chunk: int = 512, kv_chunk: int = 1024):
    """Online-softmax (flash-style) attention in pure XLA.

    q: (B, Sq, H, D); k/v: (B, Skv, H, D) — KV already expanded to the full
    head count (GQA expansion happens in the caller as a broadcast that
    GSPMD fuses with the per-shard slice, so the heads dim stays shardable
    at full TP degree; reshaping H -> (KH, G) instead makes the dim
    unshardable when the axis size exceeds KH).
    Returns (B, Sq, H, D).  Outer scan over q chunks, inner scan over kv
    chunks carrying (m, l, acc) running f32 statistics — the live score
    buffer is (B, H, q_chunk, kv_chunk).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(D)

    def attend_chunk(qc, qpos):
        """qc: (B, C, H, D) -> (B, C, H, D)."""
        C = qc.shape[1]

        def scores(kc, kvpos):
            s = jnp.einsum("bchd,bthd->bhct", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = qpos[:, None] >= kvpos[None, :]          # (C, Tc)
                s = jnp.where(mask[None, None], s, NEG_INF)
            return s

        if Skv <= kv_chunk or Skv % kv_chunk != 0:
            s = scores(k, kv_positions)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum("bhct,bthd->bhcd", p, v,
                             preferred_element_type=jnp.float32)
        else:
            nk = Skv // kv_chunk
            ks = k.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
            vs = v.reshape(B, nk, kv_chunk, H, D).transpose(1, 0, 2, 3, 4)
            kvps = kv_positions.reshape(nk, kv_chunk)

            def body(carry, xs):
                m, l, acc = carry
                kc, vc, kvpos = xs
                s = scores(kc, kvpos)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha[..., 0] + jnp.sum(p, axis=-1)
                acc = acc * alpha + jnp.einsum(
                    "bhct,bthd->bhcd", p, vc,
                    preferred_element_type=jnp.float32)
                return (m_new, l, acc), None

            m0 = jnp.full((B, H, C, 1), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, H, C), jnp.float32)
            a0 = jnp.zeros((B, H, C, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kvps))

        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3).astype(q.dtype)     # (B,C,H,D)

    if Sq <= q_chunk or Sq % q_chunk != 0:
        return attend_chunk(q, q_positions)

    n = Sq // q_chunk
    qs = q.reshape(B, n, q_chunk, H, D).transpose(1, 0, 2, 3, 4)
    ps = q_positions.reshape(n, q_chunk)

    def body(_, xs):
        qc, qpos = xs
        return None, attend_chunk(qc, qpos)

    _, outs = jax.lax.scan(body, None, (qs, ps))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D)


# --------------------------------------------------------------------------- #
# dispatch registration: "xla" backend in the kernel layout
# --------------------------------------------------------------------------- #
def flash_attention_xla(q, k, v, *, causal: bool = True, block_q=None,
                        block_k=None):
    """Kernel-layout adapter: q (B, H, S, D); k/v (B, KH, T, D)."""
    B, H, S, D = q.shape
    _, KH, T, _ = k.shape
    qt = q.transpose(0, 2, 1, 3)
    kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if KH != H:
        kt = jnp.repeat(kt, H // KH, axis=2)
        vt = jnp.repeat(vt, H // KH, axis=2)
    out = mha_chunked(qt, kt, vt, causal=causal,
                      q_positions=jnp.arange(S), kv_positions=jnp.arange(T),
                      q_chunk=int(block_q) if block_q else 512,
                      kv_chunk=int(block_k) if block_k else 1024)
    return out.transpose(0, 2, 1, 3)


def _supports(q, k, v, *, causal=True, block_q=None, block_k=None):
    return q.shape[1] % k.shape[1] == 0 and k.shape == v.shape


dispatch.register("flash_attention", "xla", priority=50,
                  supports=_supports)(flash_attention_xla)


# --------------------------------------------------------------------------- #
# depth-proportional mixed-step decode attention
#
# The reference mixed kernel materializes (B, KH, G, T, L) scores against
# the cache's full padded length L = max_len, so a prefill chunk riding
# the mixed step costs O(T * max_len) no matter how shallow the slot
# actually is — 10x+ the work of the stall-the-world prefill it replaces.
# These impls stream KV blocks through a ``lax.while_loop`` whose trip
# count is ceil(max(kv_len) / block) — a *dynamic* bound, so compute is
# proportional to the deepest live slot, exactly like the batch-1 prefill
# the chunk displaced.  Online-softmax carry per block, same masking
# contract as the reference (fully masked rows produce finite garbage).
# --------------------------------------------------------------------------- #
def mixed_decode_attention_xla(q, k, v, kv_len, *, block_k=None):
    """q: (B, KH, G, T, D); k/v: (B, KH, L, D); kv_len: (B, T) — query t
    of row b attends to cache positions < kv_len[b, t]."""
    B, KH, G, T, D = q.shape
    L = k.shape[2]
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.shape != (B, T):
        raise ValueError(
            f"mixed decode kv_len must be ({B}, {T}) — one valid length "
            f"per (row, query token); got shape {kv_len.shape}")
    blk = min(int(block_k) if block_k else 128, L)
    nb_max = -(-L // blk)
    if L % blk:
        pad = ((0, 0), (0, 0), (0, nb_max * blk - L), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    qf = q.astype(jnp.float32) / math.sqrt(D)
    nb = jnp.minimum((jnp.max(kv_len) + blk - 1) // blk, nb_max)

    def body(carry):
        i, m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * blk, blk, 2)
        vb = jax.lax.dynamic_slice_in_dim(v, i * blk, blk, 2)
        s = jnp.einsum("bkgtd,bkld->bkgtl", qf, kb.astype(jnp.float32))
        pos = i * blk + jnp.arange(blk)
        valid = pos[None, None, :] < kv_len[:, :, None]          # (B, T, blk)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        mn = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - mn[..., None])
        alpha = jnp.exp(m - mn)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgtl,bkld->bkgtd", p, vb.astype(jnp.float32))
        return i + 1, mn, l, acc

    m0 = jnp.full((B, KH, G, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, T), jnp.float32)
    a0 = jnp.zeros((B, KH, G, T, D), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(
        lambda c: c[0] < nb, body, (jnp.int32(0), m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def paged_mixed_attention_xla(q, k_pool, v_pool, block_tables, kv_len, *,
                              k_scale=None, v_scale=None):
    """q: (B, KH, G, T, D); k/v_pool: (NB, block_size, KH, D);
    block_tables: (B, pages); kv_len: (B, T).  Streams each slot's
    *logical* pages in order — no dense gather of the whole table — up to
    the deepest live slot.  With ``k_scale``/``v_scale`` ((NB, block_size,
    KH) f32) the pools are int8 and each streamed block dequantizes as it
    is sliced in."""
    B, KH, G, T, D = q.shape
    bs = k_pool.shape[1]
    pages = block_tables.shape[1]
    kv_len = jnp.asarray(kv_len, jnp.int32)
    if kv_len.shape != (B, T):
        raise ValueError(
            f"mixed decode kv_len must be ({B}, {T}) — one valid length "
            f"per (row, query token); got shape {kv_len.shape}")
    bt = block_tables.astype(jnp.int32)
    qf = q.astype(jnp.float32) / math.sqrt(D)
    nb = jnp.minimum((jnp.max(kv_len) + bs - 1) // bs, pages)

    def body(carry):
        i, m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(bt, i, 1, 1)[:, 0]    # (B,)
        kb = k_pool[ids].astype(jnp.float32)                # (B, bs, KH, D)
        vb = v_pool[ids].astype(jnp.float32)
        if k_scale is not None:
            kb = kb * k_scale[ids][..., None]               # (B, bs, KH, 1)
            vb = vb * v_scale[ids][..., None]
        s = jnp.einsum("bkgtd,blkd->bkgtl", qf, kb)
        pos = i * bs + jnp.arange(bs)
        valid = pos[None, None, :] < kv_len[:, :, None]          # (B, T, bs)
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        mn = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - mn[..., None])
        alpha = jnp.exp(m - mn)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgtl,blkd->bkgtd", p, vb)
        return i + 1, mn, l, acc

    m0 = jnp.full((B, KH, G, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, T), jnp.float32)
    a0 = jnp.zeros((B, KH, G, T, D), jnp.float32)
    _, _, l, acc = jax.lax.while_loop(
        lambda c: c[0] < nb, body, (jnp.int32(0), m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# "xla" covers the whole decode op (the 4-d single-token form aliases the
# linear-memory reference, keeping --kernel-backend xla usable), but
# auto-selection only prefers it for the 5-d mixed form — where the
# dynamic-bound streaming above beats the reference's padded-L scores.
def _mixed_only(q, *args, **kwargs):
    return q.ndim == 5


def _decode_xla(q, k, v, kv_len, *, block_k=None):
    from .ref import _decode_ref
    if q.ndim == 5:
        return mixed_decode_attention_xla(q, k, v, kv_len, block_k=block_k)
    return _decode_ref(q, k, v, kv_len, block_k=block_k)


def _decode_supports(q, k, v, kv_len, *, block_k=None):
    return q.shape[1] == k.shape[1] and k.shape == v.shape


def _paged_xla(q, k_pool, v_pool, block_tables, kv_len, *,
               k_scale=None, v_scale=None):
    from .ref import paged_decode_attention_ref
    if q.ndim == 5:
        return paged_mixed_attention_xla(q, k_pool, v_pool, block_tables,
                                         kv_len, k_scale=k_scale,
                                         v_scale=v_scale)
    return paged_decode_attention_ref(q, k_pool, v_pool, block_tables,
                                      kv_len, k_scale=k_scale,
                                      v_scale=v_scale)


def _paged_supports(q, k_pool, v_pool, block_tables, kv_len, *,
                    k_scale=None, v_scale=None):
    if (k_scale is None) != (v_scale is None):
        return False
    if k_scale is not None and k_scale.shape != k_pool.shape[:-1]:
        return False
    return (k_pool.shape == v_pool.shape and q.shape[1] == k_pool.shape[2]
            and block_tables.ndim == 2
            and block_tables.shape[0] == q.shape[0])


dispatch.register("decode_attention", "xla", priority=70,
                  supports=_decode_supports,
                  auto_gate=_mixed_only)(_decode_xla)
dispatch.register("paged_decode_attention", "xla", priority=70,
                  supports=_paged_supports,
                  auto_gate=_mixed_only)(_paged_xla)
