"""Pure-JAX model primitives shared by every architecture.

All functions are functional (params-in, activations-out) and accept a
``sub``-plan: a mapping ``sublayer-name -> LayerConfig`` used to apply the
searched strategy via ``with_sharding_constraint`` (no-op without an active
mesh, so smoke tests run unchanged on one CPU device).

Self-attention (train / prefill / decode) goes through the kernel
dispatcher (``repro.kernels.dispatch``): native Pallas on TPU, the
reference or chunked-XLA path elsewhere, selected per platform/shape and
overridable via ``REPRO_KERNEL_BACKEND``.  Cross-attention
(``kv_override``) keeps the chunked-XLA core directly — it needs
free-form KV positions the blocked kernels do not take.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import LayerConfig
from repro.core.sharding import constrain, current_mesh, fitted_pspec
from repro.kernels import dispatch as kernel_dispatch
from repro.kernels.mha_xla import mha_chunked as _mha_core  # noqa: F401
from repro.kernels.quant import quantize_kv

from .plan import node_scope

# --------------------------------------------------------------------------- #
# init helpers
# --------------------------------------------------------------------------- #
def dense_init(key, shape, dtype, fan_in: int | None = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #
def rms_norm(x: jax.Array, scale: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(dt)


def init_norm(arch, dtype):
    if arch.nonparam_norm:
        return {}
    return {"scale": jnp.zeros((arch.d_model,), dtype)}


def apply_norm(p: dict, x: jax.Array) -> jax.Array:
    return rms_norm(x, p.get("scale"))


# --------------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------------- #
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]     # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
def init_attention(key, arch, dtype):
    d, hd = arch.d_model, arch.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, arch.n_heads, hd), dtype, fan_in=d),
        "wk": dense_init(ks[1], (d, arch.n_kv_heads, hd), dtype, fan_in=d),
        "wv": dense_init(ks[2], (d, arch.n_kv_heads, hd), dtype, fan_in=d),
        "wo": dense_init(ks[3], (arch.n_heads, hd, d), dtype,
                         fan_in=arch.n_heads * hd),
    }
    if arch.qkv_bias:
        p["bq"] = jnp.zeros((arch.n_heads, hd), dtype)
        p["bk"] = jnp.zeros((arch.n_kv_heads, hd), dtype)
        p["bv"] = jnp.zeros((arch.n_kv_heads, hd), dtype)
    if arch.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attention(p: dict, x: jax.Array, arch, cfg: LayerConfig,
              *, positions: jax.Array, causal: bool = True,
              kv_cache: dict | None = None, cache_pos=None,
              block_tables: jax.Array | None = None,
              kv_override: tuple | None = None, q_chunk: int = 1024,
              use_rope: bool = True, q_lens: jax.Array | None = None):
    """GQA attention block (qkv proj + core).  ``cfg`` shards the
    (batch, seq, heads) output of the core (the searched config).

    kv_cache: {"k": (B, Smax, KH, D), "v": ...} — decode path updates it at
    ``cache_pos`` and attends over the full cache.  ``cache_pos`` is a
    scalar (all rows at the same depth) or, for single-token decode, a
    (B,) vector of per-slot positions (continuous batching: each cache
    slot carries its own request), in which case ``positions`` is (B, 1)
    and the write is a per-row scatter at ``cache_pos[b]``.
    block_tables: (B, pages) int32 — the cache is *paged*: kv_cache
    leaves are a global block pool (num_blocks, block_size, KH, D) and
    row b's logical page p lives in physical block ``block_tables[b, p]``
    (requires per-slot ``cache_pos``).
    q_lens: (B,) int32 — *mixed step*: row b's first ``q_lens[b]`` of the
    S query tokens are live (decode slots carry 1, prefill chunks up to
    S); the rest are padding whose K/V writes are dropped and whose
    outputs the caller must never sample.  Requires per-slot ``cache_pos``
    when S > 1; ignored at S == 1 (every live row is a plain
    single-token decode there, and padding rows' writes are overwritten
    before their position is ever attended).
    kv_override: (k, v, kv_positions) for cross-attention.
    Returns (attn_out_(B,S,H,D), new_cache).
    """
    B, S, _ = x.shape
    KH, G, hd = arch.n_kv_heads, arch.n_heads // arch.n_kv_heads, arch.hd

    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if kv_override is None:
        k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        if "k_norm" in p:
            k = rms_norm(k, p["k_norm"])
        if use_rope:
            k = rope(k, positions, arch.rope_theta)
        kv_positions = positions
    else:
        k, v, kv_positions = kv_override
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
    if use_rope:
        q = rope(q, positions, arch.rope_theta)

    if kv_cache is not None and q_lens is not None and S > 1:
        # Mixed step: per-slot variable query tokens.  Row b's token t is
        # live iff t < q_lens[b], sits at absolute position
        # cache_pos[b] + t, and attends causally at its own depth:
        # kv_len[b, t] = cache_pos[b] + min(t + 1, q_lens[b]).  Padding
        # tokens' K/V writes are dropped (dense: routed out of bounds;
        # paged: parked in the trash block) and their outputs are finite
        # garbage the engine never samples.
        if getattr(cache_pos, "ndim", 0) != 1:
            raise ValueError(
                "mixed-step attention requires per-slot (B,) cache_pos; "
                f"got {getattr(cache_pos, 'shape', cache_pos)}")
        q_lens = jnp.asarray(q_lens, jnp.int32)
        ck, cv = kv_cache["k"], kv_cache["v"]
        t_ar = jnp.arange(S)
        valid = t_ar[None, :] < q_lens[:, None]               # (B, S)
        idx = cache_pos[:, None] + t_ar[None, :]              # (B, S)
        kv_len = cache_pos[:, None] + jnp.minimum(t_ar + 1, q_lens[:, None])
        kd, vd = k.astype(ck.dtype), v.astype(cv.dtype)
        cks = cvs = None
        if block_tables is not None:
            NB, bs = ck.shape[0], ck.shape[1]
            pages = block_tables.shape[1]
            # clamp for the table gather only; invalid writes then
            # reroute to physical block 0 (the trash block) — clamping
            # the physical index alone could scatter into a live block
            idxc = jnp.minimum(idx, pages * bs - 1)
            blk = jnp.take_along_axis(block_tables, idxc // bs, axis=1)
            phys = jnp.where(valid, blk * bs + idxc % bs, 0)  # (B, S)
            cks = kv_cache.get("k_scale")
            cvs = kv_cache.get("v_scale")
            if cks is not None:
                # int8 pool: quantize the live rows and scatter their
                # scale rows into the flattened pool at the same slots
                kd, ks = quantize_kv(k)
                vd, vs = quantize_kv(v)
                cks = cks.reshape(NB * bs, KH).at[phys].set(ks).reshape(
                    cks.shape)
                cvs = cvs.reshape(NB * bs, KH).at[phys].set(vs).reshape(
                    cvs.shape)
                cks = constrain(cks, cfg, (None, None, "heads"))
                cvs = constrain(cvs, cfg, (None, None, "heads"))
            ck = ck.reshape(NB * bs, KH, hd).at[phys].set(kd).reshape(
                ck.shape)
            cv = cv.reshape(NB * bs, KH, hd).at[phys].set(vd).reshape(
                cv.shape)
            ck = constrain(ck, cfg, (None, None, "heads", None))
            cv = constrain(cv, cfg, (None, None, "heads", None))
        else:
            L = ck.shape[1]
            rows = jnp.arange(B)[:, None]
            safe = jnp.where(valid, idx, L)      # out of bounds -> dropped
            ck = ck.at[rows, safe].set(kd, mode="drop")
            cv = cv.at[rows, safe].set(vd, mode="drop")
            ck = constrain(ck, cfg, ("batch", "seq", "heads", None))
            cv = constrain(cv, cfg, ("batch", "seq", "heads", None))
        q = constrain(q, cfg, ("batch", "seq", "heads", None))
        H = q.shape[2]
        qg = q.transpose(0, 2, 1, 3).reshape(B, KH, H // KH, S, hd)
        if block_tables is not None:
            o = kernel_dispatch.call("paged_decode_attention", qg, ck, cv,
                                     block_tables, kv_len,
                                     k_scale=cks, v_scale=cvs)
        else:
            o = kernel_dispatch.call("decode_attention", qg,
                                     ck.transpose(0, 2, 1, 3),
                                     cv.transpose(0, 2, 1, 3), kv_len)
        o = o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
        o = constrain(o, cfg, ("batch", "seq", "heads", None))
        nc = {"k": ck, "v": cv}
        if block_tables is not None and cks is not None:
            nc["k_scale"], nc["v_scale"] = cks, cvs
        return o, nc

    if kv_cache is not None and block_tables is not None:
        # Paged decode: scatter the new token's K/V into its physical
        # block, then run the block-table-aware split-KV kernel.  The
        # pool is shared across slots, so the write indexes the token
        # axis of the flattened pool — free slots park their (ignored)
        # writes in physical block 0, the engine's trash block.
        if S != 1:
            raise ValueError(
                f"paged attention requires single-token decode (got S={S})")
        if getattr(cache_pos, "ndim", 0) != 1:
            raise ValueError(
                "paged attention requires per-slot (B,) cache_pos; got "
                f"{getattr(cache_pos, 'shape', cache_pos)}")
        ck, cv = kv_cache["k"], kv_cache["v"]
        NB, bs = ck.shape[0], ck.shape[1]
        phys = (block_tables[jnp.arange(B), cache_pos // bs] * bs
                + cache_pos % bs)                             # (B,)
        cks = kv_cache.get("k_scale")
        cvs = kv_cache.get("v_scale")
        if cks is not None:
            kq, ks = quantize_kv(k[:, 0])                    # (B, KH, hd)
            vq, vs = quantize_kv(v[:, 0])
            kd, vd = kq, vq
            cks = cks.reshape(NB * bs, KH).at[phys].set(ks).reshape(
                cks.shape)
            cvs = cvs.reshape(NB * bs, KH).at[phys].set(vs).reshape(
                cvs.shape)
            cks = constrain(cks, cfg, (None, None, "heads"))
            cvs = constrain(cvs, cfg, (None, None, "heads"))
        else:
            kd, vd = k[:, 0].astype(ck.dtype), v[:, 0].astype(cv.dtype)
        ck = ck.reshape(NB * bs, KH, hd).at[phys].set(kd).reshape(ck.shape)
        cv = cv.reshape(NB * bs, KH, hd).at[phys].set(vd).reshape(cv.shape)
        q = constrain(q, cfg, ("batch", "seq", "heads", None))
        ck = constrain(ck, cfg, (None, None, "heads", None))
        cv = constrain(cv, cfg, (None, None, "heads", None))
        H = q.shape[2]
        qg = q.reshape(B, KH, H // KH, hd)
        o = kernel_dispatch.call("paged_decode_attention", qg, ck, cv,
                                 block_tables, positions[..., -1] + 1,
                                 k_scale=cks, v_scale=cvs)
        o = o.reshape(B, 1, H, hd)
        o = constrain(o, cfg, ("batch", "seq", "heads", None))
        nc = {"k": ck, "v": cv}
        if cks is not None:
            nc["k_scale"], nc["v_scale"] = cks, cvs
        return o, nc

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache["k"], kv_cache["v"]
        if getattr(cache_pos, "ndim", 0) == 1:
            # per-slot positions: scatter row b's token at cache_pos[b]
            if S != 1:
                raise ValueError(
                    "per-slot cache_pos requires single-token decode "
                    f"(got S={S})")
            rows = jnp.arange(B)
            ck = ck.at[rows, cache_pos].set(k[:, 0].astype(ck.dtype))
            cv = cv.at[rows, cache_pos].set(v[:, 0].astype(cv.dtype))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), cache_pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv
        kv_positions = jnp.arange(ck.shape[1])
        # mask out beyond-cache positions via causality vs current position
        causal = True

    # constrain q/k/v per the searched config: (batch, seq, heads).  K/V
    # stay at their native KH width — the dispatched kernels are
    # GQA-aware, so the cache is never physically duplicated; when the
    # heads TP degree exceeds KH, ``constrain`` drops the axis (the
    # standard replicated-KV GQA fallback).
    q = constrain(q, cfg, ("batch", "seq", "heads", None))
    k = constrain(k, cfg, ("batch", "seq", "heads", None))
    v = constrain(v, cfg, ("batch", "seq", "heads", None))

    # The blocked kernels mask with 0-based contiguous positions.  That
    # matches every self-attention form except a mid-sequence cache
    # continuation (cache_pos > 0 with S > 1, where query row i sits at
    # absolute position cache_pos + i): no-cache self-attention compares
    # ``positions`` against itself (offset-invariant), prefill writes the
    # cache at a literal cache_pos == 0, and single-token decode is
    # handled as an explicit kv_len below.
    contiguous = (kv_cache is None or S == 1
                  or (isinstance(cache_pos, int) and cache_pos == 0))
    if kv_override is None and contiguous:
        # Self-attention through the dispatcher.
        H = q.shape[2]
        kh = k.shape[2]
        kt = k.transpose(0, 2, 1, 3)                       # (B, KH, T, D)
        vt = v.transpose(0, 2, 1, 3)
        if kv_cache is not None and S == 1:
            # single-token decode over the cache: split-KV kernel with the
            # GQA group as the q sublane axis (head h -> kv head h // G),
            # valid positions < pos + 1 — per slot when positions is (B, 1)
            qg = q.reshape(B, kh, H // kh, hd)             # (B, KH, G, D)
            o = kernel_dispatch.call("decode_attention", qg, kt, vt,
                                     positions[..., -1] + 1)
            o = o.reshape(B, 1, H, hd)
        else:
            # the specs ``constrain`` gave q and k/v, in kernel layout:
            # under a mesh a Pallas kernel runs shard-mapped where they
            # split no sequence and no more heads than KH
            specs = None
            if current_mesh() is not None:
                dims = ("batch", "seq", "heads", None)
                q_spec, kv_spec = (fitted_pspec(t.shape, cfg, dims)
                                   for t in (q, k))
                specs = tuple(P(sp[0], sp[2], sp[1], None)
                              for sp in (q_spec, kv_spec, kv_spec))
            o = kernel_dispatch.call(
                "flash_attention", q.transpose(0, 2, 1, 3), kt, vt,
                causal=causal, block_q=q_chunk, specs=specs)
            o = o.transpose(0, 2, 1, 3)
    else:
        # Cross-attention (free-form memory positions) and mid-sequence
        # cache continuation -> the positions-aware chunked-XLA core
        # (which wants KV expanded to the full head count).
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        o = _mha_core(q, k, v, causal=causal, q_positions=positions,
                      kv_positions=kv_positions, q_chunk=q_chunk)
    o = constrain(o, cfg, ("batch", "seq", "heads", None))
    return o, new_cache


def attention_out(p: dict, attn: jax.Array, cfg: LayerConfig) -> jax.Array:
    """o-proj: (B,S,H,D) -> (B,S,d_model); cfg shards (batch,seq,d_model)."""
    y = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
    return constrain(y, cfg, ("batch", "seq", "d_model"))


# --------------------------------------------------------------------------- #
# dense SwiGLU MLP (two graph nodes: mlp_in, mlp_out)
# --------------------------------------------------------------------------- #
def init_mlp(key, arch, dtype, d_ff: int | None = None):
    d = arch.d_model
    f = d_ff or arch.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], (d, f), dtype, fan_in=d),
        "wg": dense_init(ks[1], (d, f), dtype, fan_in=d),
        "wo": dense_init(ks[2], (f, d), dtype, fan_in=f),
    }


def mlp(p: dict, x: jax.Array, sub: dict) -> jax.Array:
    """``sub`` is the pattern layer's sublayer plan: the ``wi``/``wg``
    products and the activation are node ``mlp_in``, ``wo`` is
    ``mlp_out``."""
    with node_scope(sub, "mlp_in") as cfg:
        h = jnp.einsum("bsd,df->bsf", x, p["wi"])
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = jax.nn.silu(g) * h
        h = constrain(h, cfg, ("batch", "seq", "d_ff"))
    with node_scope(sub, "mlp_out") as cfg:
        y = jnp.einsum("bsf,fd->bsd", h, p["wo"])
        return constrain(y, cfg, ("batch", "seq", "d_model"))


# --------------------------------------------------------------------------- #
# embedding / head
# --------------------------------------------------------------------------- #
def init_embed(key, arch, dtype):
    return {"table": embed_init(key, (arch.vocab, arch.d_model), dtype)}


def embed(p: dict, tokens: jax.Array, cfg: LayerConfig) -> jax.Array:
    y = jnp.take(p["table"], tokens, axis=0)
    return constrain(y, cfg, ("batch", "seq", "d_model"))


def init_lm_head(key, arch, dtype):
    if arch.tie_embeddings:
        return {}
    return {"w": dense_init(key, (arch.d_model, arch.vocab), dtype,
                            fan_in=arch.d_model)}


def lm_head(p: dict, x: jax.Array, embed_p: dict, arch,
            cfg: LayerConfig) -> jax.Array:
    w = embed_p["table"].T if arch.tie_embeddings else p["w"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    return constrain(logits, cfg, ("batch", "seq", "vocab"))
