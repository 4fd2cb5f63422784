"""Encoder-decoder model (seamless-m4t): bidirectional encoder over stubbed
modality frame embeddings + causal decoder with per-layer cross-attention.

Reuses the decoder-only unit machinery (`lm.run_stack`); the encoder output
is threaded to every decoder layer as cross-attention memory.  In the
computation graph the memory "flows along" the decoder chain (see
graph_export) so the elimination DP sees a chain, not a fan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sharding import constrain

from . import layers as L
from . import lm
from .arch import ArchConfig
from .plan import ModelPlan, uniform_plan
from .plan import _enc_view  # encoder seen as period-1 attn+dense arch


def init_encdec(key, arch: ArchConfig, dtype=jnp.float32) -> dict:
    k_in, k_enc, k_dec, k_embed, k_head = jax.random.split(key, 5)
    enc_arch = _enc_view(arch)
    return {
        # frontend stub: frame embeddings arrive precomputed; a linear
        # adapter maps them into the encoder width.
        "enc_in": {"w": L.dense_init(k_in, (arch.d_model, arch.d_model), dtype)},
        "enc_stack": lm.init_stack(k_enc, enc_arch, arch.enc_layers, dtype),
        "enc_norm": L.init_norm(arch, dtype),
        "embed": L.init_embed(k_embed, arch, dtype),
        "stack": lm.init_stack(k_dec, arch, arch.n_units, dtype,
                               cross_attn=True),
        "final_norm": L.init_norm(arch, dtype),
        "lm_head": L.init_lm_head(k_head, arch, dtype),
    }


def encode(params, frames: jax.Array, arch: ArchConfig, plan: ModelPlan,
           *, q_chunk=512):
    """frames: (B, S_enc, D) stubbed embeddings -> (B, S_enc, D) memory."""
    enc_arch = _enc_view(arch)
    h = frames @ params["enc_in"]["w"]
    h = constrain(h, plan.enc_embed, ("batch", "seq", "d_model"))
    positions = jnp.arange(h.shape[1])
    h, _, _ = lm.run_stack(h, params["enc_stack"], enc_arch,
                           plan.enc_segments, positions=positions,
                           causal=False, q_chunk=q_chunk)
    return L.apply_norm(params["enc_norm"], h)


def forward(params, batch: dict, arch: ArchConfig,
            plan: ModelPlan | None = None, *, q_chunk=512, remat=True):
    """batch: {"frames": (B, S_enc, D), "tokens": (B, S_dec)}."""
    plan = plan if plan is not None else uniform_plan(arch)
    memory = encode(params, batch["frames"], arch, plan, q_chunk=q_chunk)
    mpos = jnp.arange(memory.shape[1])
    tokens = batch["tokens"]
    h = lm.embed_tokens(params, tokens, plan)
    positions = jnp.arange(tokens.shape[1])
    h, aux, _ = lm.run_stack(h, params["stack"], arch, plan.segments,
                             positions=positions, causal=True,
                             memory=(memory, mpos), q_chunk=q_chunk,
                             remat=remat)
    return lm.head(params, lm.final_norm(params, h, plan), arch, plan), aux


def loss_fn(params, batch: dict, arch: ArchConfig,
            plan: ModelPlan | None = None, *, q_chunk=512, remat=True,
            loss_chunk=512):
    plan = plan if plan is not None else uniform_plan(arch)
    memory = encode(params, batch["frames"], arch, plan, q_chunk=q_chunk)
    mpos = jnp.arange(memory.shape[1])
    tokens = batch["tokens"]
    h = lm.embed_tokens(params, tokens, plan)
    positions = jnp.arange(tokens.shape[1])
    h, aux, _ = lm.run_stack(h, params["stack"], arch, plan.segments,
                             positions=positions, causal=True,
                             memory=(memory, mpos), q_chunk=q_chunk,
                             remat=remat)
    h = lm.final_norm(params, h, plan)
    loss, metrics = lm.chunked_lm_loss(h[:, :-1, :], tokens[:, 1:],
                                       params, arch, plan, chunk=loss_chunk)
    metrics["loss"] = loss
    return loss, metrics


def prefill(params, batch: dict, cache: dict, arch: ArchConfig,
            plan: ModelPlan | None = None, *, q_chunk=512):
    """Encode + prefill the decoder self-attn cache; returns
    (last_logits, cache) where cache carries the memory for decode."""
    plan = plan if plan is not None else uniform_plan(arch)
    memory = encode(params, batch["frames"], arch, plan, q_chunk=q_chunk)
    mpos = jnp.arange(memory.shape[1])
    tokens = batch["tokens"]
    h = lm.embed_tokens(params, tokens, plan)
    positions = jnp.arange(tokens.shape[1])
    h, _, cache_dec = lm.run_stack(
        h, params["stack"], arch, plan.segments, positions=positions,
        causal=True, cache=cache["dec"], cache_pos=0,
        memory=(memory, mpos), q_chunk=q_chunk, remat=False)
    h = lm.final_norm(params, h[:, -1:, :], plan)
    return lm.head(params, h, arch, plan), {"dec": cache_dec, "memory": memory}


def decode_step(params, token: jax.Array, cache: dict, pos,
                arch: ArchConfig, plan: ModelPlan | None = None):
    plan = plan if plan is not None else uniform_plan(arch)
    memory = cache["memory"]
    mpos = jnp.arange(memory.shape[1])
    h = lm.embed_tokens(params, token, plan)
    positions, cache_pos = lm.decode_positions(pos, token.shape[0])
    h, _, cache_dec = lm.run_stack(
        h, params["stack"], arch, plan.segments, positions=positions,
        causal=True, cache=cache["dec"], cache_pos=cache_pos,
        memory=(memory, mpos), remat=False)
    h = lm.final_norm(params, h, plan)
    return lm.head(params, h, arch, plan), {"dec": cache_dec, "memory": memory}


def init_cache(arch: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, enc_len: int = 0) -> dict:
    cache = {"dec": lm.init_cache(arch, batch, max_len, dtype)}
    if enc_len:
        cache["memory"] = jnp.zeros((batch, enc_len, arch.d_model), dtype)
    return cache
