"""Blockwise flash attention for TPU (Pallas), fwd + bwd, causal/full,
GQA-aware.

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

Backward (FlashAttention-2, arXiv:2307.08691, re-blocked the same way): a
``dq`` pass over (q head, q block) programs and a ``dk/dv`` pass over
(KV head, KV block) programs, each streaming the other side's blocks
along a sequential grid axis into f32 VMEM accumulators and skipping
blocks above the causal diagonal.  The forward keeps its single output,
so the ``dq`` pass finds each row's logsumexp itself (a first sweep over
the KV blocks) and also emits it with ``D_i = rowsum(dO * O)`` for the
``dk/dv`` pass, as rows (lane-dense along the query axis); that pass
works on transposed score tiles (KV rows x query lanes) and loops over
its G query heads, so K/V are never repeated to H heads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.sharding import entry_axes

from . import dispatch, mha_xla

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
# backward: dq pass, then dk/dv pass
# --------------------------------------------------------------------------- #
STATS_ROWS = 8          # row 0 logsumexp, row 1 D_i: one sublane tile


def _scores(a, b, scale):
    """a @ b.T * scale in f32, both (rows, D)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, dq_ref, stats_ref,
                   m_ref, l_ref, di_ref, acc_ref, *, scale: float,
                   causal: bool, block_q: int, block_k: int, kv_steps: int):
    """Grid step j < kv_steps: online row max/sum over KV block j (the
    forward's statistics); j >= kv_steps: dq += dS K over block j -
    kv_steps.  Writes dq and the rows (logsumexp, D_i) at the end."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    q_start = qi * block_q
    k_start = (j % kv_steps) * block_k
    run = k_start <= q_start + block_q - 1 if causal else True

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        di_ref[...] = jnp.sum(do_ref[0, 0].astype(jnp.float32)
                              * o_ref[0, 0].astype(jnp.float32),
                              axis=-1, keepdims=True)

    def scores():
        s = _scores(q_ref[0, 0].astype(jnp.float32),
                    k_ref[0, 0].astype(jnp.float32), scale)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        return s

    @pl.when(jnp.logical_and(j < kv_steps, run))
    def _row_stats():
        s = scores()
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new)
                      + jnp.sum(jnp.exp(s - m_new), -1, keepdims=True))
        m_ref[...] = m_new

    @pl.when(j == kv_steps)
    def _logsumexp():
        m_ref[...] = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))

    @pl.when(jnp.logical_and(j >= kv_steps, run))
    def _accumulate():
        p = jnp.exp(scores() - m_ref[...])
        dp = _scores(do_ref[0, 0].astype(jnp.float32),
                     v_ref[0, 0].astype(jnp.float32), 1.0)
        ds = p * (dp - di_ref[...])
        acc_ref[...] += jax.lax.dot(ds, k_ref[0, 0].astype(jnp.float32),
                                    preferred_element_type=jnp.float32)

    @pl.when(j == 2 * kv_steps - 1)
    def _finalize():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, (block_q, 128), 1)
        cols = jnp.where(lane == 0, m_ref[...],
                         jnp.where(lane == 1, di_ref[...], 0.0))
        stats_ref[0, 0] = cols.T[:STATS_ROWS]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, *, scale: float, causal: bool,
                    block_q: int, block_k: int, q_steps: int):
    """Grid step j: query block j % q_steps of the KV head's query head
    j // q_steps.  Score tiles are transposed (KV rows x query lanes), so
    the row statistics broadcast along sublanes and dV += P^T dO, dK +=
    dS^T Q are plain products."""
    ki = pl.program_id(1)
    j = pl.program_id(2)
    q_start = (j % q_steps) * block_q
    k_start = ki * block_k
    run = k_start <= q_start + block_q - 1 if causal else True

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(run)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)                 # (bq, d)
        do = do_ref[0, 0].astype(jnp.float32)
        st = _scores(k_ref[0, 0].astype(jnp.float32), q, scale)   # (bk, bq)
        if causal:
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_k, block_q), 0)
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_k, block_q), 1)
            st = jnp.where(qpos >= kpos, st, NEG_INF)
        stats = stats_ref[0, 0]                              # (8, bq)
        pt = jnp.exp(st - stats[0:1])
        dpt = _scores(v_ref[0, 0].astype(jnp.float32), do, 1.0)
        dst = pt * (dpt - stats[1:2])
        dv_acc[...] += jax.lax.dot(pt, do, preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot(dst, q, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, do, *, causal: bool = True,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """Gradients of :func:`flash_attention` at (q, k, v) given its output
    ``o`` and the output cotangent ``do`` -> (dq, dk, dv) in the layouts
    and dtypes of q, k, v."""
    B, H, S, D = q.shape
    _, KH, T, _ = k.shape
    G = H // KH
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    q_steps, kv_steps = S // block_q, T // block_k
    scale = 1.0 / math.sqrt(D)
    params = compat.tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    # dq pass: program (b*H + h, qi); step j sweeps KV block j % kv_steps
    # twice.  Blocks above the diagonal repeat the last one needed, so
    # their skipped steps fetch nothing.
    def kv_of_q(bh, qi, j):
        ki = j % kv_steps
        if causal:
            ki = jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k)
        return bh // H, (bh % H) // G, ki, 0

    def q_of_q(bh, qi, j):
        return bh // H, bh % H, qi, 0

    q_spec = pl.BlockSpec((1, 1, block_q, D), q_of_q)
    kv_spec = pl.BlockSpec((1, 1, block_k, D), kv_of_q)
    dq, stats = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_steps=kv_steps),
        grid=(B * H, q_steps, 2 * kv_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 1, STATS_ROWS, block_q),
                                lambda bh, qi, j: (bh // H, bh % H, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, STATS_ROWS, S), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m, then logsumexp
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, 1), jnp.float32),   # D_i
            pltpu.VMEM((block_q, D), jnp.float32),   # dq
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, o, do)

    # dk/dv pass: program (b*KH + kh, ki); step j is query head
    # kh*G + j // q_steps, query block j % q_steps.  Blocks above the
    # diagonal repeat the first one needed.
    def q_row(bkh, ki, j):
        qi = j % q_steps
        if causal:
            qi = jnp.minimum(jnp.maximum(qi, ki * block_k // block_q),
                             q_steps - 1)
        return bkh // KH, (bkh % KH) * G + j // q_steps, qi

    def q_of_kv(bkh, ki, j):
        return (*q_row(bkh, ki, j), 0)

    def stats_of_kv(bkh, ki, j):
        b, h, qi = q_row(bkh, ki, j)
        return b, h, 0, qi

    def kv_of_kv(bkh, ki, j):
        return bkh // KH, bkh % KH, ki, 0

    q_spec = pl.BlockSpec((1, 1, block_q, D), q_of_kv)
    kv_spec = pl.BlockSpec((1, 1, block_k, D), kv_of_kv)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, q_steps=q_steps),
        grid=(B * KH, kv_steps, G * q_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec,
                  pl.BlockSpec((1, 1, STATS_ROWS, block_q), stats_of_kv)],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),   # dk
            pltpu.VMEM((block_k, D), jnp.float32),   # dv
        ],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, stats)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# dispatch registration: "pallas" (native TPU) and "interpret" backends
# --------------------------------------------------------------------------- #
_PREF_Q = (512, 256, 128, 64, 32, 16, 8)
_PREF_K = (512, 256, 128, 64, 32, 16, 8)


def _blocks(q, k, block_q, block_k) -> tuple[int, int]:
    """(block_q, block_k): the caller's, capped at the lengths, else the
    largest preferred block dividing each length."""
    S, T = q.shape[2], k.shape[2]
    bq = (min(block_q, S) if block_q
          else dispatch.block_candidates(S, _PREF_Q)[0])
    bk = (min(block_k, T) if block_k
          else dispatch.block_candidates(T, _PREF_K)[0])
    return bq, bk


def _supports(q, k, v, *, causal=True, block_q=None, block_k=None):
    B, H, S, D = q.shape
    _, KH, T, _ = k.shape
    if H % KH != 0 or k.shape != v.shape:
        return False
    bq, bk = _blocks(q, k, block_q, block_k)
    return S % bq == 0 and T % bk == 0


def _supports_native(q, k, v, *, causal=True, block_q=None, block_k=None):
    # Mosaic needs MXU-aligned score tiles: block_q on the sublane axis
    # (x8), block_k on the lane axis (x128).  Unaligned lengths (e.g. a
    # prime S, where the only valid block is S itself) must fall back to
    # the xla/ref backends instead of failing TPU compilation.
    if not _supports(q, k, v, causal=causal, block_q=block_q,
                     block_k=block_k):
        return False
    bq, bk = _blocks(q, k, block_q, block_k)
    return bq % 8 == 0 and bk % 128 == 0


# The backward puts both block sizes on the lane axis (query blocks in
# the dk/dv pass's transposed tiles and in the statistics rows, KV blocks
# in the dq pass's tiles), so both are multiples of 128; the interpreter
# keeps the same rule so that CPU runs take the path the chip takes.
# Larger blocks win on a v5e (fewer grid steps; 1024 x 1024 took 3.7 ms
# against 4.4 ms at 512 x 512 for the granite cell's shape), up to
# (block x head_dim) tiles of 128K elements, past which the dq pass runs
# out of VMEM (head_dim 256 at 1024).
_PREF_BWD = (1024, 512, 256, 128)
_BWD_TILE_ELEMS = 1024 * 128


def _bwd_blocks(q, k) -> tuple[int, int] | None:
    """The backward's (block_q, block_k): the largest preferred
    lane-aligned block dividing each sequence length; None where a
    length admits none."""
    pref = tuple(b for b in _PREF_BWD if b * q.shape[3] <= _BWD_TILE_ELEMS)
    bqs, bks = ([b for b in dispatch.block_candidates(n, pref)
                 if b % 128 == 0] for n in (q.shape[2], k.shape[2]))
    return (bqs[0], bks[0]) if bqs and bks else None


def _backward(q, k, v, o, do, *, causal, interpret):
    blocks = _bwd_blocks(q, k)
    if blocks is None:
        dispatch.record_selection("flash_attention_bwd", "reference")
        _, vjp = jax.vjp(functools.partial(mha_xla.flash_attention_xla,
                                           causal=causal), q, k, v)
        return vjp(do)
    dispatch.record_selection("flash_attention_bwd", "pallas")
    bq, bk = blocks
    return flash_attention_bwd(q, k, v, o, do, causal=causal, block_q=bq,
                               block_k=bk, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _grad_ready(causal, block_q, block_k, interpret):
    """The forward kernel with the Pallas backward as its VJP (the
    reference VJP where the shapes admit no backward blocks)."""
    fwd = functools.partial(flash_attention, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)

    @jax.custom_vjp
    def attend(q, k, v):
        return fwd(q, k, v)

    def attend_fwd(q, k, v):
        o = fwd(q, k, v)
        return o, (q, k, v, o)

    def attend_bwd(res, do):
        return _backward(*res, do, causal=causal, interpret=interpret)

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


def _via_pallas(q, k, v, *, causal=True, block_q=None, block_k=None,
                interpret=False):
    bq, bk = _blocks(q, k, block_q, block_k)
    return _grad_ready(causal, bq, bk, interpret)(q, k, v)


def _local_specs(sizes, specs, q, k, v, **kwargs):
    """Shard-by-shard attention under a mesh: batch split alike on q and
    k/v, heads split alike and no further than KH divides (so each
    device's query heads attend to its own KV heads: head h -> h // G
    holds per shard), sequence and head_dim whole.  None otherwise, e.g.
    a sequence split, or heads split past KH (the replicated-KV GQA
    fallback), where a shard would need other shards' keys."""
    q_spec, k_spec, v_spec = (tuple(s) + (None,) * (4 - len(s))
                              for s in specs)
    if k_spec != v_spec or q_spec[0] != k_spec[0] or q_spec[1] != k_spec[1]:
        return None
    if any(e is not None for e in q_spec[2:] + k_spec[2:]):
        return None
    heads = math.prod(sizes[a] for a in entry_axes(q_spec[1]))
    if q.shape[1] % heads or k.shape[1] % heads:
        return None
    return tuple(P(*s) for s in (q_spec, k_spec, v_spec)), P(*q_spec)


dispatch.register("flash_attention", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_native, spmd_safe=False,
                  local_specs=_local_specs)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("flash_attention", "interpret",
                  priority=20, supports=_supports, spmd_safe=False,
                  local_specs=_local_specs)(
    functools.partial(_via_pallas, interpret=True))
