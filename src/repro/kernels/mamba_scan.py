"""Mamba-1 selective-scan backends for the ``mamba_scan`` dispatch op.

Canonical layout (the model's natural one — batch-major, time second):

    mamba_scan(dt, B, C, x, A, D, *, chunk, initial_state, return_state)
        dt/x: (B, S, di); B/C: (B, S, N); A: (di, N) (negative);
        D: (di,); initial_state: (B, di, N) f32 or None
        -> y (B, S, di) [, final_state (B, di, N) f32]

The recurrence (discretized selective SSM, f32 state math):

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + (dt_t · x_t) ⊗ B_t;   y_t = h_t · C_t + D ⊙ x_t

Backends registered here:

* ``ref``     — chunk-checkpointed sequential scan (the oracle; bwd memory
  O(S/chunk · state)).  This is the path ``repro.models.recurrent`` hand-
  rolled before the op existed, moved behind the dispatcher verbatim.
* ``xla``     — chunked *associative* scan: within each time chunk the
  linear recurrence (a, b) ∘ (a', b') = (a·a', b·a' + b') runs as a
  parallel ``lax.associative_scan`` (O(log chunk) depth instead of O(chunk)
  sequential steps); the carry crosses chunks through an outer scan, so
  peak memory stays O(chunk · di · N) and the stateful decode form works.
* ``pallas`` / ``interpret`` — fused TPU kernel: the (N, di) state lives in
  VMEM scratch in f32 and is carried across a sequential chunk grid
  dimension (same grid-revisiting idiom as the WKV6 kernel); dt/B/C/x
  stream HBM->VMEM chunk by chunk, so the O(S·di·N) discretized terms are
  never materialized.  Stateless form only (no initial state in, no final
  state out) — the decode path stays on ref/xla; bwd via reference VJP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.core.scan import remat_time_scan

from . import dispatch


def _step(A, Dskip):
    """A: (di, N); Dskip: (di,).  The (B, di, N) discretized terms are
    formed per step inside the scan — materializing them for the whole
    sequence is O(S·di·N) and exactly what the fused kernel avoids."""

    def step(h, xs):
        dt, Bm, Cm, x1 = xs          # (B,di), (B,N), (B,N), (B,di)
        dt = dt.astype(jnp.float32)  # xs stream in bf16; state math in f32
        Bm = Bm.astype(jnp.float32)
        Cm = Cm.astype(jnp.float32)
        x1 = x1.astype(jnp.float32)
        dtA = dt[..., None] * A      # (B, di, N)
        h = jnp.exp(dtA) * h + (dt * x1)[..., None] * Bm[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, Cm) + Dskip * x1
        return h, y

    return step


def mamba_scan_ref(dt, Bm, Cm, x, A, Dskip, *, chunk: int = 64,
                   initial_state=None, return_state: bool = False):
    """Sequential chunk-checkpointed scan (the oracle)."""
    B, S, di = x.shape
    N = Bm.shape[-1]
    A = A.astype(jnp.float32)
    Dskip = Dskip.astype(jnp.float32)
    h0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((B, di, N), jnp.float32))
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    hN, y = remat_time_scan(_step(A, Dskip), h0,
                            (tm(dt), tm(Bm), tm(Cm), tm(x)), chunk=chunk)
    y = jnp.moveaxis(y, 0, 1).astype(x.dtype)                 # (B, S, di)
    return (y, hN) if return_state else y


def mamba_scan_xla(dt, Bm, Cm, x, A, Dskip, *, chunk: int = 64,
                   initial_state=None, return_state: bool = False):
    """Chunked associative scan: parallel within a chunk, carried across.
    An uneven tail (S % chunk) runs as one short extra chunk, so peak
    memory stays O(chunk · di · N) for every sequence length."""
    B, S, di = x.shape
    N = Bm.shape[-1]
    Af = A.astype(jnp.float32)
    Df = Dskip.astype(jnp.float32)
    h = (initial_state.astype(jnp.float32) if initial_state is not None
         else jnp.zeros((B, di, N), jnp.float32))
    chunk = min(chunk, S)
    n, rem = divmod(S, chunk)
    lead = n * chunk

    @jax.checkpoint
    def chunk_body(h, xs):
        dtc, Bc, Cc, xc = (a.astype(jnp.float32) for a in xs)  # (B, c, ...)
        a = jnp.exp(dtc[..., None] * Af)                   # (B, c, di, N)
        b = (dtc * xc)[..., None] * Bc[:, :, None, :]      # (B, c, di, N)

        def combine(lhs, rhs):
            al, bl = lhs
            ar, br = rhs
            return al * ar, bl * ar + br

        aa, bb = jax.lax.associative_scan(combine, (a, b), axis=1)
        h_all = aa * h[:, None] + bb                       # (B, c, di, N)
        y = jnp.einsum("bcdn,bcn->bcd", h_all, Cc) + Df * xc
        return h_all[:, -1], y

    parts = []
    if lead:
        split = lambda a: jnp.moveaxis(
            a[:, :lead].reshape(B, n, chunk, *a.shape[2:]), 1, 0)
        h, y = jax.lax.scan(chunk_body, h,
                            (split(dt), split(Bm), split(Cm), split(x)))
        parts.append(jnp.moveaxis(y, 0, 1).reshape(B, lead, di))
    if rem:
        h, y_tail = chunk_body(
            h, tuple(a[:, lead:] for a in (dt, Bm, Cm, x)))
        parts.append(y_tail)
    y = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    y = y.astype(x.dtype)
    return (y, h) if return_state else y


dispatch.register("mamba_scan", "ref", priority=60)(mamba_scan_ref)
dispatch.register("mamba_scan", "xla", priority=50)(mamba_scan_xla)


# --------------------------------------------------------------------------- #
# Pallas kernel: state (N, block_d) f32 in VMEM scratch — channels on the
# lane axis (multiples of 128), N on the sublane axis.  The recurrence is
# independent per channel, so the grid tiles d_inner: a published width
# (16384 for jamba) never has to fit VMEM whole.
# --------------------------------------------------------------------------- #
_PREF_D = (512, 256, 128)


def _mamba_kernel(dt_ref, b_ref, c_ref, x_ref, at_ref, d_ref, o_ref, h_ref,
                  *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    N = h_ref.shape[0]
    At = at_ref[...].astype(jnp.float32)                  # (N, bd)
    Dv = d_ref[...].astype(jnp.float32)                   # (1, bd)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def col(row):
        # (1, N) -> (N, 1) as a masked lane reduction: Mosaic has no
        # relayout from a lane vector to a sublane column
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def step(t, h):
        def row(ref):
            return ref[0, pl.ds(t, 1), :].astype(jnp.float32)

        dt, xt = row(dt_ref), row(x_ref)                  # (1, bd)
        bt, ct = col(row(b_ref)), col(row(c_ref))         # (N, 1)
        h = jnp.exp(At * dt) * h + bt * (dt * xt)
        y = jnp.sum(h * ct, axis=0, keepdims=True) + Dv * xt
        o_ref[0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan_pallas(dt, Bm, Cm, x, A, Dskip, *, chunk: int = 64,
                      interpret: bool = False):
    """Stateless fused form; dt/x: (B, S, di); B/C: (B, S, N)."""
    B, S, di = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    bd = dispatch.block_candidates(di, _PREF_D)[0]
    grid = (B, di // bd, S // chunk)

    kernel = functools.partial(_mamba_kernel, chunk=chunk)
    chan = pl.BlockSpec((1, chunk, bd), lambda b, di_, ci: (b, ci, di_))
    state_in = pl.BlockSpec((1, chunk, N), lambda b, di_, ci: (b, ci, 0))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[chan, state_in, state_in, chan,
                  pl.BlockSpec((N, bd), lambda b, di_, ci: (0, di_)),
                  pl.BlockSpec((1, bd), lambda b, di_, ci: (0, di_))],
        out_specs=chan,
        out_shape=jax.ShapeDtypeStruct((B, S, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, Bm, Cm, x, A.T, Dskip.reshape(1, di))


# The kernel carries no initial state and does not emit the final state, so
# it is only eligible for the stateless ``return_state=False`` form; the
# ref/xla backends cover the stateful decode path.
def _supports(dt, Bm, Cm, x, A, Dskip, *, chunk=64, initial_state=None,
              return_state=False):
    if initial_state is not None or return_state:
        return False
    S = x.shape[1]
    return S % min(chunk, S) == 0


def _supports_native(dt, Bm, Cm, x, A, Dskip, *, chunk=64, initial_state=None,
                     return_state=False):
    # Mosaic wants lane-aligned (N, di) state tiles; unaligned widths fall
    # back to ref/xla instead of failing TPU compilation.
    if not _supports(dt, Bm, Cm, x, A, Dskip, chunk=chunk,
                     initial_state=initial_state, return_state=return_state):
        return False
    di, N = x.shape[-1], Bm.shape[-1]
    return di % 128 == 0 and N % 8 == 0


@functools.lru_cache(maxsize=None)
def _grad_ready(chunk, interpret):
    kern = functools.partial(mamba_scan_pallas, chunk=chunk,
                             interpret=interpret)
    ref_fn = functools.partial(mamba_scan_xla, chunk=chunk)
    return dispatch.with_reference_vjp(kern, ref_fn)


def _via_pallas(dt, Bm, Cm, x, A, Dskip, *, chunk=64, initial_state=None,
                return_state=False, interpret=False):
    del initial_state, return_state  # unsupported; gated by _supports
    return _grad_ready(min(chunk, x.shape[1]), interpret)(
        dt, Bm, Cm, x, A, Dskip)


dispatch.register("mamba_scan", "pallas", platforms=("tpu",),
                  priority=100, supports=_supports_native,
                  spmd_safe=False)(
    functools.partial(_via_pallas, interpret=False))
dispatch.register("mamba_scan", "interpret", priority=20,
                  supports=_supports, spmd_safe=False)(
    functools.partial(_via_pallas, interpret=True))
