"""RWKV6 (WKV6) recurrence kernel for TPU (Pallas).

TPU adaptation of the Finch recurrence (arXiv:2404.05892): the (N x N)
per-head state lives in VMEM scratch in f32 and is carried across a
*sequential* chunk grid dimension (the same grid-revisiting idiom as flash
attention); r/k/v/w stream HBM->VMEM chunk by chunk.  Within a chunk the
recurrence is stepped with ``fori_loop`` outer products — numerically exact
(the chunked-parallel GLA form needs cumulative-decay exponentials that
under/overflow in bf16 for w^64; the sequential-in-VMEM form does not).

Layout: r/k/v/w (B, H, T, N) with N the head size (64); u (H, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

from . import dispatch


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
                 chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    N = state_ref.shape[0]
    u = u_ref[0].astype(jnp.float32)                     # (1, N)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def col(row):
        # (1, N) -> (N, 1) as a masked lane reduction: Mosaic has no
        # relayout from a lane vector to a sublane column
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, S):
        def row(ref):
            return ref[0, 0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, N)

        rt, kt, vt, wt = row(r_ref), row(k_ref), row(v_ref), row(w_ref)
        # o_t = r_t (S + diag(u) k_t^T v_t) = r_t S + (r_t . u k_t) v_t
        o = (jnp.sum(col(rt) * S, axis=0, keepdims=True)
             + jnp.sum(rt * u * kt, axis=1, keepdims=True) * vt)
        o_ref[0, 0, pl.ds(t, 1), :] = o.astype(o_ref.dtype)
        return col(wt) * S + col(kt) * vt

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
         u: jax.Array, *, chunk: int = 64,
         interpret: bool = False) -> jax.Array:
    """r/k/v/w: (B, H, T, N); u: (H, N) -> out (B, H, T, N)."""
    B, H, T, N = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    n_chunks = T // chunk
    grid = (B * H, n_chunks)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, n_chunks=n_chunks)

    def spec(ref_kind: str):
        if ref_kind == "seq":
            return pl.BlockSpec((1, 1, chunk, N),
                                lambda bh, ci: (bh // H, bh % H, ci, 0))
        # u rides as (H, 1, N): a (1, N) block of an (H, N) array would
        # break Mosaic's rule that the trailing block dims tile or match
        return pl.BlockSpec((1, 1, N), lambda bh, ci: (bh % H, 0, 0))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec("seq"), spec("seq"), spec("seq"), spec("seq"),
                  spec("u")],
        out_specs=spec("seq"),
        out_shape=jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, N))


# --------------------------------------------------------------------------- #
# dispatch registration: "pallas" (native TPU) and "interpret" backends.
# The kernel carries no initial state and does not emit the final state, so
# it is only eligible for the stateless ``return_state=False`` form; the
# "ref" backend (chunk-checkpointed scan) covers the stateful decode path.
# --------------------------------------------------------------------------- #
def _supports(r, k, v, w, u, *, chunk=64, initial_state=None,
              return_state=False):
    if initial_state is not None or return_state:
        return False
    T = r.shape[2]
    return T % min(chunk, T) == 0


@functools.lru_cache(maxsize=None)
def _grad_ready(chunk, interpret):
    from . import ref
    kern = functools.partial(wkv6, chunk=chunk, interpret=interpret)
    return dispatch.with_reference_vjp(kern, ref.wkv6_scan)


def _via_pallas(r, k, v, w, u, *, chunk=64, initial_state=None,
                return_state=False, interpret=False):
    del initial_state, return_state  # unsupported; gated by _supports
    return _grad_ready(chunk, interpret)(r, k, v, w, u)


dispatch.register("wkv6", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("wkv6", "interpret", priority=20, supports=_supports,
                  spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
