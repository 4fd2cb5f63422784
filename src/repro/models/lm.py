"""Decoder-only LM assembly (dense / MoE / RWKV / Mamba-hybrid / VLM).

Params are a pytree with every per-layer array stacked over **pattern
units** (leading dim ``n_units``); the forward pass slices the stack per
plan segment and ``lax.scan``s each segment, applying that segment's
sublayer configs via sharding constraints.  Attention and the WKV6
recurrence execute through ``repro.kernels.dispatch`` (selected per
platform/shape; force with ``REPRO_KERNEL_BACKEND`` or
``TrainConfig.kernel_backend``).

Entry points:
  init_lm(rng, arch, dtype)                      -> params
  forward(params, batch, arch, plan)             -> (logits, aux)
  loss_fn(params, batch, arch, plan)             -> (loss, metrics)
  init_cache(arch, batch, max_len, dtype)        -> cache
  init_paged_cache(arch, num_blocks, block_size, batch, dtype) -> cache
  prefill(params, batch, cache, arch, plan)      -> (logits_last, cache)
  decode_step(params, token, cache, pos, arch, plan[, block_tables])
                                                 -> (logits, cache)
  step(params, tokens, cache, pos, arch, plan[, q_lens, block_tables])
                                                 -> (logits, cache)
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.config import LayerConfig
from repro.core.sharding import constrain

from . import layers as L
from . import moe as M
from . import recurrent as Rc
from .arch import ArchConfig
from .plan import (ModelPlan, Segment, UnitPlan, node_scope, sublayer_keys,
                   uniform_plan)

BSD = ("batch", "seq", "d_model")


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def _init_layer(key, arch: ArchConfig, spec, dtype) -> dict:
    ks = iter(jax.random.split(key, 8))
    p: dict = {"ln1": L.init_norm(arch, dtype), "ln2": L.init_norm(arch, dtype)}
    if spec.mixer == "attn":
        p["attn"] = L.init_attention(next(ks), arch, dtype)
    elif spec.mixer == "mamba":
        p["ssm"] = Rc.init_mamba(next(ks), arch, dtype)
    elif spec.mixer == "rwkv":
        p["tmix"] = Rc.init_rwkv_tmix(next(ks), arch, dtype)
    if spec.mixer == "rwkv":
        p["cmix"] = Rc.init_rwkv_cmix(next(ks), arch, dtype)
    elif spec.ffn == "moe":
        p["moe"] = M.init_moe(next(ks), arch, dtype)
    else:
        p["mlp"] = L.init_mlp(next(ks), arch, dtype)
    return p


def _init_unit(key, arch: ArchConfig, dtype, cross_attn: bool = False) -> dict:
    ks = jax.random.split(key, arch.period)
    unit = {}
    for j, spec in enumerate(arch.pattern):
        lp = _init_layer(ks[j], arch, spec, dtype)
        if cross_attn:
            kx = jax.random.fold_in(ks[j], 7)
            lp["ln_x"] = L.init_norm(arch, dtype)
            lp["xattn"] = L.init_attention(kx, arch, dtype)
        unit[f"l{j}"] = lp
    return unit


def init_stack(key, arch: ArchConfig, n_units: int, dtype,
               cross_attn: bool = False) -> dict:
    keys = jax.random.split(key, n_units)
    return jax.vmap(lambda k: _init_unit(k, arch, dtype, cross_attn))(keys)


def init_lm(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    k_embed, k_stack, k_head, k_front = jax.random.split(key, 4)
    params = {
        "embed": L.init_embed(k_embed, arch, dtype),
        "stack": init_stack(k_stack, arch, arch.n_units, dtype),
        "final_norm": L.init_norm(arch, dtype),
        "lm_head": L.init_lm_head(k_head, arch, dtype),
    }
    return params


# --------------------------------------------------------------------------- #
# one pattern-unit forward (shared by train / prefill / decode)
# --------------------------------------------------------------------------- #
def unit_forward(h, unit_params, arch: ArchConfig, unit_plan: UnitPlan,
                 *, positions, causal=True, cache=None, cache_pos=None,
                 block_tables=None, q_lens=None, memory=None,
                 memory_positions=None, q_chunk=512, time_chunk=64):
    """Returns (h, aux_loss, new_cache).

    q_lens: (B,) int32 — mixed serving step: only row b's first
    ``q_lens[b]`` of the S tokens are live; attention drops padding K/V
    writes and the recurrent mixers make padding state-transparent."""
    aux = 0.0
    new_cache: dict = {}
    for j, spec in enumerate(arch.pattern):
        lp = unit_params[f"l{j}"]
        sub = unit_plan[j]
        lc = cache.get(f"l{j}") if cache is not None else None
        nc: dict = {}

        with node_scope(sub, "ln1") as cfg:
            hn = constrain(L.apply_norm(lp["ln1"], h), cfg, BSD)
        if spec.mixer == "attn":
            with node_scope(sub, "attn") as cfg:
                a, kvc = L.attention(
                    lp["attn"], hn, arch, cfg, positions=positions,
                    causal=causal, kv_cache=(lc or {}).get("kv"),
                    cache_pos=cache_pos, block_tables=block_tables,
                    q_lens=q_lens, q_chunk=q_chunk)
            with node_scope(sub, "attn_out") as cfg:
                y = L.attention_out(lp["attn"], a, cfg)
            if kvc is not None:
                nc["kv"] = kvc
        elif spec.mixer == "mamba":
            with node_scope(sub, "ssm") as cfg:
                if cache is None:
                    # hierarchical remat: during the unit's bwd recompute
                    # only one mixer's scan internals are live at a time
                    y = jax.checkpoint(
                        lambda p_, h_: Rc.mamba_mix(
                            p_, h_, arch, cfg, chunk=time_chunk)[0],
                        policy=jax.checkpoint_policies.nothing_saveable,
                    )(lp["ssm"], hn)
                else:
                    y, st = Rc.mamba_mix(lp["ssm"], hn, arch, cfg,
                                         state=lc.get("ssm_state"),
                                         chunk=time_chunk, q_lens=q_lens)
                    nc["ssm_state"] = st
        elif spec.mixer == "rwkv":
            with node_scope(sub, "tmix") as cfg:
                if cache is None:
                    y = jax.checkpoint(
                        lambda p_, h_: Rc.rwkv_tmix(
                            p_, h_, arch, cfg, chunk=time_chunk)[0],
                        policy=jax.checkpoint_policies.nothing_saveable,
                    )(lp["tmix"], hn)
                else:
                    y, st = Rc.rwkv_tmix(lp["tmix"], hn, arch, cfg,
                                         state=lc.get("tmix_state"),
                                         chunk=time_chunk, q_lens=q_lens)
                    nc["tmix_state"] = st
        else:
            raise ValueError(spec.mixer)
        with node_scope(sub, "add1") as cfg:
            h = constrain(h + y, cfg, BSD)

        if memory is not None:
            with node_scope(sub, "ln_x") as cfg:
                hx = constrain(L.apply_norm(lp["ln_x"], h), cfg, BSD)
            with node_scope(sub, "xattn") as cfg:
                mem_h, mpos = memory
                mk = jnp.einsum("bsd,dhe->bshe", mem_h, lp["xattn"]["wk"])
                mv = jnp.einsum("bsd,dhe->bshe", mem_h, lp["xattn"]["wv"])
                a, _ = L.attention(
                    lp["xattn"], hx, arch, cfg, positions=positions,
                    causal=False, kv_override=(mk, mv, mpos),
                    q_chunk=q_chunk, use_rope=False)
            with node_scope(sub, "xattn_out") as cfg:
                y = L.attention_out(lp["xattn"], a, cfg)
            with node_scope(sub, "add_x") as cfg:
                h = constrain(h + y, cfg, BSD)

        with node_scope(sub, "ln2") as cfg:
            hn = constrain(L.apply_norm(lp["ln2"], h), cfg, BSD)
        if spec.mixer == "rwkv":
            with node_scope(sub, "cmix") as cfg:
                y, st = Rc.rwkv_cmix(lp["cmix"], hn, arch, cfg,
                                     state=(lc or {}).get("cmix_state"),
                                     q_lens=q_lens)
            if cache is not None:
                nc["cmix_state"] = st
        elif spec.ffn == "moe":
            with node_scope(sub, "moe") as cfg:
                if cache is None:
                    # hierarchical remat: one MoE layer's dispatch buffers
                    # live at a time during the unit's bwd recompute
                    y, a_loss = jax.checkpoint(
                        lambda p_, h_: M.moe_ffn(p_, h_, arch, cfg),
                        policy=jax.checkpoint_policies.nothing_saveable,
                    )(lp["moe"], hn)
                else:
                    y, a_loss = M.moe_ffn(lp["moe"], hn, arch, cfg)
            aux = aux + a_loss
        else:
            y = L.mlp(lp["mlp"], hn, sub)
        with node_scope(sub, "add2") as cfg:
            h = constrain(h + y, cfg, BSD)
        new_cache[f"l{j}"] = nc
    return h, aux, new_cache


REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "dots_batch": jax.checkpoint_policies.checkpoint_dots,
}


@jax.named_scope("stack")
def run_stack(h, stack_params, arch: ArchConfig, segments, *, positions,
              causal=True, cache=None, cache_pos=None, block_tables=None,
              q_lens=None, memory=None, q_chunk=512, time_chunk=64,
              remat=True, remat_policy="nothing"):
    """Scan the unit stack segment by segment; returns (h, aux, new_cache).

    All of it runs under the ``stack`` scope, so the scan's slicing of
    stacked weights and saved activations lands there, outside every
    unit's sublayer scopes."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache_parts = []

    for seg in segments:
        seg_params = jax.tree.map(lambda a: a[seg.start:seg.end], stack_params)

        if cache is None:
            def body(carry, unit_params, _plan=seg.plan):
                h, aux = carry
                h, aux_u, _ = unit_forward(
                    h, unit_params, arch, _plan, positions=positions,
                    causal=causal, memory=memory, q_chunk=q_chunk,
                    time_chunk=time_chunk)
                return (h, aux + aux_u), None

            if remat:
                body = jax.checkpoint(
                    body, policy=REMAT_POLICIES[remat_policy])
            (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), seg_params)
        else:
            seg_cache = jax.tree.map(lambda a: a[seg.start:seg.end], cache)

            def body(carry, xs, _plan=seg.plan):
                h, aux = carry
                unit_params, unit_cache = xs
                h, aux_u, nc = unit_forward(
                    h, unit_params, arch, _plan, positions=positions,
                    causal=causal, cache=unit_cache, cache_pos=cache_pos,
                    block_tables=block_tables, q_lens=q_lens, memory=memory,
                    q_chunk=q_chunk, time_chunk=time_chunk)
                return (h, aux + aux_u), nc

            (h, aux_total), seg_new_cache = jax.lax.scan(
                body, (h, aux_total), (seg_params, seg_cache))
            new_cache_parts.append(seg_new_cache)

    new_cache = None
    if cache is not None and new_cache_parts:
        new_cache = jax.tree.map(
            lambda *parts: jnp.concatenate(parts, axis=0), *new_cache_parts)
    return h, aux_total, new_cache


# --------------------------------------------------------------------------- #
# the graph nodes outside the unit (shared by train / prefill / decode)
# --------------------------------------------------------------------------- #
def embed_tokens(params, tokens: jax.Array, plan: ModelPlan) -> jax.Array:
    with node_scope(plan, "embed") as cfg:
        return L.embed(params["embed"], tokens, cfg)


def final_norm(params, h: jax.Array, plan: ModelPlan) -> jax.Array:
    with node_scope(plan, "final_norm") as cfg:
        return constrain(L.apply_norm(params["final_norm"], h), cfg, BSD)


def head(params, h: jax.Array, arch: ArchConfig, plan: ModelPlan):
    with node_scope(plan, "lm_head") as cfg:
        return L.lm_head(params["lm_head"], h, params["embed"], arch, cfg)


# --------------------------------------------------------------------------- #
# full forward / loss
# --------------------------------------------------------------------------- #
def hidden_states(params, batch: dict, arch: ArchConfig,
                  plan: ModelPlan, *, q_chunk=512, time_chunk=64,
                  remat=True, remat_policy="nothing"):
    """Embed + layer stack + final norm -> ((B, S, D), aux_loss)."""
    tokens = batch["tokens"]
    h = embed_tokens(params, tokens, plan)
    if arch.frontend and "frontend" in batch:
        h = jnp.concatenate([batch["frontend"].astype(h.dtype), h], axis=1)
    S = h.shape[1]
    positions = jnp.arange(S)
    h, aux, _ = run_stack(h, params["stack"], arch, plan.segments,
                          positions=positions, causal=True, q_chunk=q_chunk,
                          time_chunk=time_chunk, remat=remat,
                          remat_policy=remat_policy)
    return final_norm(params, h, plan), aux


def forward(params, batch: dict, arch: ArchConfig, plan: ModelPlan | None = None,
            *, q_chunk=512, time_chunk=64, remat=True,
            remat_policy="nothing"):
    """batch: {"tokens": (B, S_text) [, "frontend": (B, F, D)]}.

    Returns (logits (B, S, V), aux_loss).  For frontend archs the patch/frame
    embeddings are prepended: S = F + S_text.
    """
    plan = plan if plan is not None else uniform_plan(arch)
    h, aux = hidden_states(params, batch, arch, plan, q_chunk=q_chunk,
                           time_chunk=time_chunk, remat=remat,
                           remat_policy=remat_policy)
    return head(params, h, arch, plan), aux


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 z_loss_coef: float = 1e-4):
    """Causal-LM cross entropy in f32 with z-loss; returns (loss, metrics)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    z = jnp.square(lse)
    loss = jnp.mean(nll) + z_loss_coef * jnp.mean(z)
    acc = jnp.mean((jnp.argmax(lf, axis=-1) == labels).astype(jnp.float32))
    return loss, {"nll": jnp.mean(nll), "accuracy": acc}


def chunked_lm_loss(h: jax.Array, labels: jax.Array, params, arch: ArchConfig,
                    plan: ModelPlan, *, chunk: int = 512,
                    z_loss_coef: float = 1e-4):
    """Memory-efficient causal-LM loss: logits are produced and consumed in
    seq chunks (rematerialized in bwd), never materializing the full
    (B, S, V) tensor — at 1M-token global batches that tensor is hundreds
    of TB and must not exist.

    h: (B, T, D) hidden states; labels: (B, T) next-token targets.  The
    head and the cross entropy are one graph node, ``lm_head``.
    """
    B, T, D = h.shape
    n = T // chunk if (T % chunk == 0 and T > chunk) else 1
    c = T // n

    with node_scope(plan, "lm_head") as cfg:
        w = (params["embed"]["table"].T if arch.tie_embeddings
             else params["lm_head"]["w"])
        hs = jnp.moveaxis(h.reshape(B, n, c, D), 1, 0)
        ls = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)

        @jax.checkpoint
        def body(carry, xs):
            hc, lc = xs
            logits = jnp.einsum("bcd,dv->bcv", hc, w)
            logits = constrain(logits, cfg, ("batch", "seq", "vocab"))
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            gold = jnp.take_along_axis(lf, lc[..., None], axis=-1)[..., 0]
            hit = (jnp.argmax(lf, axis=-1) == lc).astype(jnp.float32)
            nll, z, acc = carry
            return (nll + jnp.sum(lse - gold), z + jnp.sum(jnp.square(lse)),
                    acc + jnp.sum(hit)), None

        zero = jnp.zeros((), jnp.float32)
        (nll, z, acc), _ = jax.lax.scan(body, (zero, zero, zero), (hs, ls))
    count = B * T
    loss = nll / count + z_loss_coef * z / count
    return loss, {"nll": nll / count, "accuracy": acc / count}


def loss_fn(params, batch: dict, arch: ArchConfig,
            plan: ModelPlan | None = None, *, aux_coef: float = 0.01,
            q_chunk=512, time_chunk=64, remat=True, loss_chunk=512,
            remat_policy="nothing"):
    plan = plan if plan is not None else uniform_plan(arch)
    h, aux = hidden_states(params, batch, arch, plan, q_chunk=q_chunk,
                           time_chunk=time_chunk, remat=remat,
                           remat_policy=remat_policy)
    tokens = batch["tokens"]
    # frontend positions carry no labels: score only the text segment
    h_text = h[:, -tokens.shape[1]:, :]
    loss, metrics = chunked_lm_loss(h_text[:, :-1, :], tokens[:, 1:],
                                    params, arch, plan, chunk=loss_chunk)
    loss = loss + aux_coef * aux
    metrics["aux"] = aux
    metrics["loss"] = loss
    return loss, metrics


# --------------------------------------------------------------------------- #
# serving: cache init / prefill / decode
# --------------------------------------------------------------------------- #
def init_cache(arch: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> dict:
    KH, hd, D = arch.n_kv_heads, arch.hd, arch.d_model
    H, hs = arch.n_rwkv_heads, arch.rwkv_head_size
    di, N = arch.d_inner, arch.ssm_state
    n = arch.n_units
    cache: dict = {}
    for j, spec in enumerate(arch.pattern):
        c: dict = {}
        if spec.mixer == "attn":
            c["kv"] = {
                "k": jnp.zeros((n, batch, max_len, KH, hd), dtype),
                "v": jnp.zeros((n, batch, max_len, KH, hd), dtype),
            }
        elif spec.mixer == "mamba":
            c["ssm_state"] = {
                "conv": jnp.zeros((n, batch, arch.ssm_conv - 1, di), dtype),
                "ssm": jnp.zeros((n, batch, di, N), jnp.float32),
            }
        elif spec.mixer == "rwkv":
            c["tmix_state"] = {
                "shift": jnp.zeros((n, batch, D), dtype),
                "wkv": jnp.zeros((n, batch, H, hs, hs), jnp.float32),
            }
            c["cmix_state"] = {"shift": jnp.zeros((n, batch, D), dtype)}
        cache[f"l{j}"] = c
    return cache


def init_paged_cache(arch: ArchConfig, num_blocks: int, block_size: int,
                     batch: int, dtype=jnp.bfloat16,
                     kv_quant: str | None = None) -> dict:
    """Paged variant of :func:`init_cache`: KV leaves are one global pool
    of ``num_blocks`` fixed-size blocks ``(n_units, NB, block_size, KH,
    hd)`` shared by all slots through a block table, instead of a dense
    ``max_len`` row per slot.  Recurrent (mamba / wkv6 / shift) state is
    O(1) in sequence length and stays slot-dense ``(n_units, batch,
    ...)`` exactly as in the dense cache.

    ``kv_quant="int8"`` stores the pool as int8 with per-token-slot
    per-head f32 scales riding in the same ``kv`` subtree (``k_scale`` /
    ``v_scale``, shape ``(n_units, NB, block_size, KH)``): the write
    paths quantize row-wise on scatter, the paged attention backends
    dequantize after the block-table gather.  Zero-initialized scales
    dequantize never-written slots to exactly 0.0, same as the fp pool.
    """
    if kv_quant not in (None, "none", "int8"):
        raise ValueError(f"unknown kv_quant {kv_quant!r}")
    quant = kv_quant == "int8"
    dense = init_cache(arch, batch, 1, dtype)
    KH, hd, n = arch.n_kv_heads, arch.hd, arch.n_units
    pool_dtype = jnp.int8 if quant else dtype

    def kv_pool():
        leaves = {
            "k": jnp.zeros((n, num_blocks, block_size, KH, hd), pool_dtype),
            "v": jnp.zeros((n, num_blocks, block_size, KH, hd), pool_dtype)}
        if quant:
            leaves["k_scale"] = jnp.zeros(
                (n, num_blocks, block_size, KH), jnp.float32)
            leaves["v_scale"] = jnp.zeros(
                (n, num_blocks, block_size, KH), jnp.float32)
        return leaves

    cache: dict = {}
    for lkey, c in dense.items():
        cache[lkey] = {k: (kv_pool() if k == "kv" else v)
                       for k, v in c.items()}
    return cache


def prefill(params, batch: dict, cache: dict, arch: ArchConfig,
            plan: ModelPlan | None = None, *, q_chunk=512, time_chunk=64):
    """Process the prompt, filling ``cache``; returns (last_logits, cache)."""
    plan = plan if plan is not None else uniform_plan(arch)
    tokens = batch["tokens"]
    h = embed_tokens(params, tokens, plan)
    if arch.frontend and "frontend" in batch:
        h = jnp.concatenate([batch["frontend"].astype(h.dtype), h], axis=1)
    S = h.shape[1]
    positions = jnp.arange(S)
    h, _, cache = run_stack(h, params["stack"], arch, plan.segments,
                            positions=positions, causal=True, cache=cache,
                            cache_pos=0, q_chunk=q_chunk,
                            time_chunk=time_chunk, remat=False)
    h = final_norm(params, h[:, -1:, :], plan)
    return head(params, h, arch, plan), cache


def decode_positions(pos, batch: int):
    """Normalize a decode ``pos`` argument -> (rope_positions, cache_pos).

    Accepted forms:
      * scalar — every row sits at the same position (the static-batch
        lockstep form); rope positions are (1,), broadcast over batch.
      * ``(batch,)`` vector — per-slot positions (continuous batching:
        each cache slot carries its own request at its own depth); rope
        positions are (B, 1) and cache writes scatter at ``pos[b]``.

    Anything else is rejected loudly: the old behaviour silently accepted
    a ``(B,)`` array and built shape-(1, B) positions via
    ``jnp.asarray(pos)[None]``, producing wrong RoPE angles for every row.
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return pos[None], pos
    if pos.ndim == 1 and pos.shape[0] == batch:
        return pos[:, None], pos
    raise ValueError(
        f"decode pos must be a scalar or a ({batch},) vector matching the "
        f"token batch; got shape {pos.shape}")


def decode_step(params, token: jax.Array, cache: dict, pos,
                arch: ArchConfig, plan: ModelPlan | None = None, *,
                block_tables: jax.Array | None = None):
    """One decode step.  token: (B, 1) int32; pos: scalar int32 (current
    position = number of tokens already in the cache) or a (B,) vector of
    per-slot positions (see :func:`decode_positions`).  With
    ``block_tables`` ((B, pages) int32) the cache's KV leaves are the
    paged block pool from :func:`init_paged_cache`; requires (B,)
    per-slot positions."""
    plan = plan if plan is not None else uniform_plan(arch)
    h = embed_tokens(params, token, plan)
    positions, cache_pos = decode_positions(pos, token.shape[0])
    if block_tables is not None and cache_pos.ndim != 1:
        raise ValueError("paged decode (block_tables) requires a (B,) "
                         "per-slot pos vector")
    h, _, cache = run_stack(h, params["stack"], arch, plan.segments,
                            positions=positions, causal=True, cache=cache,
                            cache_pos=cache_pos, block_tables=block_tables,
                            remat=False)
    return head(params, final_norm(params, h, plan), arch, plan), cache


def step(params, tokens: jax.Array, cache: dict, pos, arch: ArchConfig,
         plan: ModelPlan | None = None, *, q_lens: jax.Array | None = None,
         block_tables: jax.Array | None = None, q_chunk=512, time_chunk=64):
    """One unified mixed step: every slot advances a variable number of
    tokens in a single ragged batch (Sarathi-style chunked prefill riding
    the decode batch).

    tokens: (B, T) int32 — row b's live tokens occupy columns
    ``[0, q_lens[b])``, the rest is padding; pos: scalar (broadcast) or
    (B,) int32, row b's current cache depth; q_lens: (B,) int32 or None
    (None means every row advances all T tokens — at T == 1 this is
    exactly :func:`decode_step`).  Decoding slots contribute 1 token,
    admitting slots a prefill chunk of up to T, idle slots 0.  With
    ``block_tables`` the KV leaves are the paged pool from
    :func:`init_paged_cache`.

    Returns (logits (B, T, V), cache).  Row b's next-token logits sit at
    ``logits[b, q_lens[b] - 1]``; rows with ``q_lens[b] == 0`` and padding
    columns hold finite garbage the caller must not sample.
    """
    plan = plan if plan is not None else uniform_plan(arch)
    B, T = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (B,))
    elif pos.shape != (B,):
        raise ValueError(
            f"step pos must be a scalar or a ({B},) vector matching the "
            f"token batch; got shape {pos.shape}")
    if q_lens is not None:
        q_lens = jnp.asarray(q_lens, jnp.int32)
        if q_lens.shape != (B,):
            raise ValueError(
                f"step q_lens must be a ({B},) vector matching the token "
                f"batch; got shape {q_lens.shape}")
    elif T > 1:
        q_lens = jnp.full((B,), T, jnp.int32)
    h = embed_tokens(params, tokens, plan)
    positions = pos[:, None] + jnp.arange(T)[None, :]
    h, _, cache = run_stack(h, params["stack"], arch, plan.segments,
                            positions=positions, causal=True, cache=cache,
                            cache_pos=pos, block_tables=block_tables,
                            q_lens=q_lens, q_chunk=q_chunk,
                            time_chunk=time_chunk, remat=False)
    return head(params, final_norm(params, h, plan), arch, plan), cache
