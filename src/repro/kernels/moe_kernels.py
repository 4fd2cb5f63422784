"""MoE token dispatch + expert FFN + combine backends for the
``moe_dispatch_combine`` dispatch op.

Canonical signature (routing — router matmul, top-k, gate normalization,
aux loss — stays in the model; this op owns everything after it):

    moe_dispatch_combine(x, gate_vals, expert_idx, wi, wg, wo, *,
                         capacity, constrain)
        x: (B, S, D); gate_vals/expert_idx: (B, S, K) (idx int32);
        wi/wg: (E, D, F); wo: (E, F, D) -> y (B, S, D)

GShard capacity semantics are part of the op contract: each (token, k)
assignment gets a position within its expert *per batch row* (the group =
the data shard); positions >= capacity are dropped (contribute zero — the
residual carries them).  All backends implement identical drop semantics,
so they agree to float tolerance.

``constrain`` is an optional callback ``(array, dim_names) -> array``
applying the caller's sharding constraints (the model passes a closure
over its LayerConfig) — the kernel package stays ignorant of plan/config
types while the SPMD annotations GSPMD needs stay exactly where the
hand-rolled implementation had them.

Backends registered here:

* ``xla``  — scatter/gather into per-group (E*C, D) buffers (the
  production path, moved verbatim from ``repro.models.moe``): dispatch
  loops over the K routing choices so the (B, S, D)-sized scatter source
  is never replicated K times, and every tensor touching the
  scatter/gather is batch-constrained (without that GSPMD replicates the
  cotangents — 4 GiB full-batch f32 buffers observed in the 398B dry-run).
* ``ref``  — capacity-bucketed dense einsum (the classic TPU MoE
  formulation and the allclose oracle): a one-hot (B, S, E*C) dispatch
  tensor contracted against x and, after the expert FFN, against the
  gates.  O(B·S·E·C) memory — an ``auto_gate`` keeps auto-selection on
  the scatter path beyond small shapes.
* ``pallas`` / ``interpret`` — the scatter and gather run as Pallas
  kernels (sequential read-modify-write into a VMEM-resident (E*C+1, D)
  buffer per batch row, token indices scalar-prefetched through SMEM);
  the expert einsums between them stay in XLA where the MXU already runs
  them optimally.  Bwd via reference VJP against the dense oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch


def _identity_constrain(a, dims):
    del dims
    return a


def _positions(expert_idx, E: int, C: int):
    """Per-group expert slot assignment.

    expert_idx: (B, S, K) int32 -> (lin (B, S*K) int32 flat buffer index
    with dropped tokens mapped to the trash slot E*C, keep (B, S*K) bool).
    """
    B, S, K = expert_idx.shape
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)    # (B, S, K, E)
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                      # (B, S*K, E)
    pos_in_expert = jnp.sum(pos * flat, axis=-1)               # (B, S*K)
    eidx = expert_idx.reshape(B, S * K)
    keep = pos_in_expert < C
    lin = jnp.where(keep, eidx * C + pos_in_expert, E * C)     # drop slot
    return lin, keep


def _expert_ffn(buf, wi, wg, wo, cs):
    """buf: (B, E, C, D) -> (B, E, C, D) SwiGLU expert FFN."""
    h = jnp.einsum("becd,edf->becf", buf, wi)
    g = jnp.einsum("becd,edf->becf", buf, wg)
    h = jax.nn.silu(g) * h
    h = cs(h, ("batch", "expert", None, "d_ff"))
    out = jnp.einsum("becf,efd->becd", h, wo)
    return cs(out, ("batch", "expert", None, "d_model"))


def moe_scatter_xla(x, gate_vals, expert_idx, wi, wg, wo, *,
                    capacity: int, constrain=None):
    """Scatter/gather dispatch (the production path on every platform)."""
    cs = constrain or _identity_constrain
    B, S, D = x.shape
    E = wi.shape[0]
    K = expert_idx.shape[-1]
    C = capacity

    lin, keep = _positions(expert_idx, E, C)
    lin = cs(lin, ("batch", None)).reshape(B, S, K)
    keep_k = keep.reshape(B, S, K)
    b_idx = jnp.arange(B)[:, None]
    buf = jnp.zeros((B, E * C + 1, D), x.dtype)
    for k in range(K):
        src = x * keep_k[..., k, None].astype(x.dtype)
        src = cs(src, ("batch", "seq", "d_model"))
        buf = buf.at[b_idx, lin[:, :, k]].add(src)
    buf = cs(buf, ("batch", None, "d_model"))
    buf = buf[:, :-1].reshape(B, E, C, D)
    buf = cs(buf, ("batch", "expert", None, "d_model"))

    out = _expert_ffn(buf, wi, wg, wo, cs)

    # combine: gather back (local), weight by gate values, K at a time
    out = out.reshape(B, E * C, D)
    out = cs(out, ("batch", None, "d_model"))
    gates_k = (keep_k * gate_vals.reshape(B, S, K)).astype(x.dtype)
    y = jnp.zeros((B, S, D), x.dtype)
    for k in range(K):
        g_k = out[b_idx, jnp.minimum(lin[:, :, k], E * C - 1)]
        g_k = cs(g_k, ("batch", "seq", "d_model"))
        y = y + g_k * gates_k[..., k, None]
    return cs(y, ("batch", "seq", "d_model"))


def moe_dense_ref(x, gate_vals, expert_idx, wi, wg, wo, *,
                  capacity: int, constrain=None):
    """Capacity-bucketed dense-einsum dispatch (the oracle)."""
    cs = constrain or _identity_constrain
    B, S, D = x.shape
    E = wi.shape[0]
    K = expert_idx.shape[-1]
    C = capacity

    lin, keep = _positions(expert_idx, E, C)
    # one-hot over E*C+1 slots; the trash column is sliced off, so dropped
    # assignments vanish from both dispatch and combine.
    oh = jax.nn.one_hot(lin, E * C + 1, dtype=x.dtype)[..., :-1]
    disp = oh.reshape(B, S, K, E * C)
    # top-k experts are distinct per token, so the K slot rows never
    # collide and a plain sum folds them into one (B, S, E*C) map.
    disp_tok = disp.sum(axis=2)
    buf = jnp.einsum("bse,bsd->bed", disp_tok, x).reshape(B, E, C, D)
    buf = cs(buf, ("batch", "expert", None, "d_model"))

    out = _expert_ffn(buf, wi, wg, wo, cs)

    comb = jnp.einsum("bske,bsk->bse", disp,
                      gate_vals.astype(x.dtype))             # (B, S, E*C)
    y = jnp.einsum("bse,bed->bsd", comb, out.reshape(B, E * C, D))
    return cs(y, ("batch", "seq", "d_model"))


_MAX_REF_SLOTS = 1 << 22   # B*S*E*C elements in the dense dispatch tensor


def _ref_small(x, gate_vals, expert_idx, wi, wg, wo, *, capacity,
               constrain=None):
    B, S, _ = x.shape
    return B * S * wi.shape[0] * capacity <= _MAX_REF_SLOTS


dispatch.register("moe_dispatch_combine", "xla", priority=60)(moe_scatter_xla)
dispatch.register("moe_dispatch_combine", "ref", priority=50,
                  auto_gate=_ref_small)(moe_dense_ref)


# --------------------------------------------------------------------------- #
# Pallas scatter / gather kernels.  Grid (B,): each program owns one batch
# row, the (E*C+1, D) dispatch buffer sits in VMEM, and the S*K token
# indices arrive through SMEM so the sequential read-modify-write loop can
# address the buffer with scalars.
# --------------------------------------------------------------------------- #
def _scatter_kernel(lin_ref, x_ref, buf_ref, *, S: int, K: int):
    buf_ref[...] = jnp.zeros_like(buf_ref)

    def body(i, _):
        s, k = i // K, i % K
        idx = lin_ref[0, s, k]
        # dropped tokens all land on the trash row (sliced off outside);
        # kept slots are unique, and the loop is sequential, so the
        # read-modify-write never races.
        buf_ref[0, idx] = (buf_ref[0, idx]
                           + x_ref[0, s].astype(buf_ref.dtype))
        return 0

    jax.lax.fori_loop(0, S * K, body, 0)


def _gather_kernel(lin_ref, out_ref, gate_ref, y_ref, *, S: int, K: int,
                   n_slots: int):
    def body(s, _):
        acc = jnp.zeros((y_ref.shape[-1],), jnp.float32)
        for k in range(K):                       # K is small and static
            idx = jnp.minimum(lin_ref[0, s, k], n_slots - 1)
            acc = acc + (out_ref[0, idx].astype(jnp.float32)
                         * gate_ref[0, s, k])
        y_ref[0, s] = acc.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, S, body, 0)


def _moe_pallas_impl(x, gate_vals, expert_idx, wi, wg, wo, *,
                     capacity: int, constrain=None, interpret: bool = False):
    cs = constrain or _identity_constrain
    B, S, D = x.shape
    E = wi.shape[0]
    K = expert_idx.shape[-1]
    C = capacity
    n_slots = E * C

    lin, keep = _positions(expert_idx, E, C)
    lin = lin.reshape(B, S, K).astype(jnp.int32)
    smem = pl.BlockSpec((1, S, K), lambda b: (b, 0, 0),
                        memory_space=pltpu.SMEM)

    buf = pl.pallas_call(
        functools.partial(_scatter_kernel, S=S, K=K),
        grid=(B,),
        in_specs=[smem, pl.BlockSpec((1, S, D), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, n_slots + 1, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_slots + 1, D), x.dtype),
        interpret=interpret,
    )(lin, x)
    buf = buf[:, :-1].reshape(B, E, C, D)
    buf = cs(buf, ("batch", "expert", None, "d_model"))

    out = _expert_ffn(buf, wi, wg, wo, cs).reshape(B, n_slots, D)

    gates_k = (keep.reshape(B, S, K) * gate_vals).astype(jnp.float32)
    y = pl.pallas_call(
        functools.partial(_gather_kernel, S=S, K=K, n_slots=n_slots),
        grid=(B,),
        in_specs=[smem,
                  pl.BlockSpec((1, n_slots, D), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, S, K), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, S, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, D), x.dtype),
        interpret=interpret,
    )(lin, out, gates_k)
    return cs(y, ("batch", "seq", "d_model"))


_MAX_VMEM_BUF = 4 << 20    # f32 bytes of the per-row VMEM dispatch buffer


def _supports_pallas(x, gate_vals, expert_idx, wi, wg, wo, *, capacity,
                     constrain=None):
    B, S, D = x.shape
    E = wi.shape[0]
    K = expert_idx.shape[-1]
    slots = E * capacity + 1
    return (slots * D * 4 <= _MAX_VMEM_BUF
            and S * D * 4 <= _MAX_VMEM_BUF
            and S * K <= 8192)                # SMEM index budget


def _via_pallas(x, gate_vals, expert_idx, wi, wg, wo, *, capacity,
                constrain=None, interpret=False):
    kern = functools.partial(_moe_pallas_impl, capacity=capacity,
                             constrain=constrain, interpret=interpret)
    ref_fn = functools.partial(moe_dense_ref, capacity=capacity,
                               constrain=constrain)
    return dispatch.with_reference_vjp(kern, ref_fn)(
        x, gate_vals, expert_idx, wi, wg, wo)


dispatch.register("moe_dispatch_combine", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_pallas,
                  spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("moe_dispatch_combine", "interpret", priority=20,
                  supports=_supports_pallas, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
