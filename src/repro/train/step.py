"""Train step builder.

``make_train_step`` returns a pure function
``(params, opt_state, batch) -> (params, opt_state, metrics)`` with:
  * the searched strategy applied via the plan's sharding constraints;
  * optional microbatch gradient accumulation (``lax.scan`` over microbatch
    slices, f32 accumulators) for global batches that exceed memory;
  * remat (configurable policy) around each scanned layer segment;
  * AdamW with ZeRO-1-shardable f32 moments.

``plan`` may be a phase-aware
:class:`~repro.plans.parallel_plan.ParallelPlan` (the ``train`` phase is
used), a bare ``ModelPlan``, or ``None`` (uniform).

``make_serve_fns`` moved to :mod:`repro.serve.fns` (it is a serving
concern); the name is re-exported here for backwards compatibility.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core.stages import StageAssignment
from repro.kernels import dispatch as kernel_dispatch
from repro.models import model_module
from repro.models.arch import ArchConfig
from repro.models.plan import ModelPlan, Segment
from repro.optim import AdamWConfig, adamw_update
from repro.plans.parallel_plan import ParallelPlan, as_model_plan

# the scope of the microbatch gradient sum, beside the model's graph nodes
ACCUMULATE = "accumulate"


@dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = field(default_factory=AdamWConfig)
    microbatches: int = 1
    q_chunk: int = 512
    time_chunk: int = 64
    remat: bool = True
    remat_policy: str = "nothing"
    loss_chunk: int = 512
    aux_coef: float = 0.01
    # force a kernel dispatch backend (pallas|interpret|xla|ref); None = auto
    kernel_backend: str | None = None


def _stage_segments(segments, start: int, end: int) -> tuple:
    """Clip the plan's segment list to units ``[start, end)`` and re-index
    relative to the stage's sliced stack."""
    out = []
    for seg in segments:
        s, e = max(seg.start, start), min(seg.end, end)
        if s < e:
            out.append(Segment(s - start, e - start, seg.plan))
    return tuple(out)


def make_train_step(arch: ArchConfig,
                    plan: ParallelPlan | ModelPlan | None = None,
                    cfg: TrainConfig | None = None):
    cfg = cfg or TrainConfig()
    stages = None
    if isinstance(plan, ParallelPlan):
        st = plan.stage_for("train")
        if st.num_stages > 1:
            stages = st
    plan = as_model_plan(plan, arch, "train")
    mod = model_module(arch)
    if stages is not None:
        return _make_staged_train_step(arch, plan, stages, cfg, mod)

    def loss(params, batch):
        kw = dict(q_chunk=cfg.q_chunk, remat=cfg.remat,
                  loss_chunk=cfg.loss_chunk)
        if mod.__name__.endswith(".lm"):
            kw["time_chunk"] = cfg.time_chunk
            kw["aux_coef"] = cfg.aux_coef
            kw["remat_policy"] = cfg.remat_policy
        return mod.loss_fn(params, batch, arch, plan, **kw)

    grad_fn = jax.value_and_grad(loss, has_aux=True)

    def _step(params, opt_state, batch):
        if cfg.microbatches <= 1:
            (l, metrics), grads = grad_fn(params, batch)
        else:
            m = cfg.microbatches

            def split(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mb = jax.tree.map(split, batch)

            def body(acc_g, mb_i):
                (l, met), g = grad_fn(params, mb_i)
                with jax.named_scope(ACCUMULATE):
                    acc_g = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), acc_g, g)
                return acc_g, met

            # derive the f32 accumulator FROM params so the (FSDP) param
            # sharding propagates to it — a fresh jnp.zeros has no sharding
            # link and XLA replicates it, all-reducing full-size grads per
            # microbatch (observed: 2.9 TB/dev/step on olmoe, see §Perf).
            with jax.named_scope(ACCUMULATE):
                zeros = jax.tree.map(
                    lambda x: (x * 0).astype(jnp.float32), params)
            grads, mets = jax.lax.scan(body, zeros, mb)
            with jax.named_scope(ACCUMULATE):
                grads = jax.tree.map(lambda g: g / m, grads)
            metrics = jax.tree.map(lambda v: jnp.mean(v, axis=0), mets)

        new_params, new_state, om = adamw_update(
            params, grads, opt_state, cfg.optimizer)
        metrics = dict(metrics)
        metrics.update(om)
        return new_params, new_state, metrics

    def train_step(params, opt_state, batch):
        # backend selection happens at trace time, so the context applies
        # inside jit; a no-op when kernel_backend is None (auto-select)
        with kernel_dispatch.force_backend(cfg.kernel_backend):
            return _step(params, opt_state, batch)

    return train_step


# --------------------------------------------------------------------------- #
# pipeline-staged (1F1B) train step
# --------------------------------------------------------------------------- #
def _make_staged_train_step(arch: ArchConfig, plan: ModelPlan,
                            stages: StageAssignment, cfg: TrainConfig, mod):
    """1F1B microbatched step for a plan whose train phase has ``S > 1``
    pipeline stages.

    The model splits at the plan's stage boundaries: stage 0 owns the
    embedding (plus any frontend concat) and its unit range, inner stages
    own unit ranges, the last stage owns its range plus final norm and
    the chunked LM loss (and, for tied embeddings, reads the embedding
    table — its gradient is summed into stage 0's).  Each microbatch's
    stage forwards are recorded with ``jax.vjp`` and its backward is
    scheduled as early as the data dependencies allow — warmup of
    ``S-1`` forwards, then the steady 1F1B alternation, then cooldown —
    so at most ``S`` microbatches of residuals are live at once.  The
    numerics are plain microbatch gradient accumulation (mean over
    ``stages.microbatches`` per-microbatch grads), identical to the
    single-stage step on the same batch up to float reassociation.
    """
    if not mod.__name__.endswith(".lm"):
        raise ValueError(
            f"pipeline-staged training supports decoder-only LMs only; "
            f"{arch.name} maps to {mod.__name__} "
            f"(token-level pipelining for other families is a follow-up)")
    if stages.n_units != arch.n_units:
        raise ValueError(
            f"stage assignment covers {stages.n_units} units but "
            f"{arch.name} has {arch.n_units}")
    S = stages.num_stages
    M = max(1, stages.microbatches)
    seg_lists = [_stage_segments(plan.segments, *stages.unit_range(s))
                 for s in range(S)]
    stack_kw = dict(q_chunk=cfg.q_chunk, time_chunk=cfg.time_chunk,
                    remat=cfg.remat, remat_policy=cfg.remat_policy)
    one = jnp.ones((), jnp.float32)
    aux_ct = jnp.full((), cfg.aux_coef, jnp.float32)

    def fwd_first(p, mb):
        tokens = mb["tokens"]
        h = mod.embed_tokens(p, tokens, plan)
        if arch.frontend and "frontend" in mb:
            h = jnp.concatenate([mb["frontend"].astype(h.dtype), h], axis=1)
        h, aux, _ = mod.run_stack(h, p["stack"], arch, seg_lists[0],
                                  positions=jnp.arange(h.shape[1]),
                                  causal=True, **stack_kw)
        return h, aux

    def make_mid(s):
        def fwd(p, h):
            h, aux, _ = mod.run_stack(h, p["stack"], arch, seg_lists[s],
                                      positions=jnp.arange(h.shape[1]),
                                      causal=True, **stack_kw)
            return h, aux
        return fwd

    mids = [make_mid(s) for s in range(1, S - 1)]

    def fwd_last(p, h, tokens):
        h, aux, _ = mod.run_stack(h, p["stack"], arch, seg_lists[S - 1],
                                  positions=jnp.arange(h.shape[1]),
                                  causal=True, **stack_kw)
        h = mod.final_norm(p, h, plan)
        h_text = h[:, -tokens.shape[1]:, :]
        lm_loss, met = mod.chunked_lm_loss(h_text[:, :-1, :], tokens[:, 1:],
                                           p, arch, plan,
                                           chunk=cfg.loss_chunk)
        return (lm_loss, aux), met

    def stage_params(params, s):
        b0, b1 = stages.unit_range(s)
        p = {"stack": jax.tree.map(lambda a: a[b0:b1], params["stack"])}
        if s == 0:
            p["embed"] = params["embed"]
        if s == S - 1:
            p["final_norm"] = params["final_norm"]
            # the loss reads the tied embedding table or the head weight
            if arch.tie_embeddings:
                p["embed"] = params["embed"]
            else:
                p["lm_head"] = params["lm_head"]
        return p

    def forward_mb(sp, mb):
        """All S stage forwards for one microbatch; returns the recorded
        vjps plus the scalars the backward and metrics need."""
        (h, aux0), vjp0 = jax.vjp(fwd_first, sp[0], mb)
        auxes, mvjps = [aux0], []
        for s, fwd in enumerate(mids):
            (h, aux_s), vjp_s = jax.vjp(fwd, sp[s + 1], h)
            auxes.append(aux_s)
            mvjps.append(vjp_s)
        (lm_loss, auxL), vjpL, met = jax.vjp(
            fwd_last, sp[S - 1], h, mb["tokens"], has_aux=True)
        auxes.append(auxL)
        aux = sum(auxes[1:], auxes[0])
        met = dict(met)
        met["aux"] = aux
        met["loss"] = lm_loss + cfg.aux_coef * aux
        return (vjp0, mvjps, vjpL), met

    def backward_mb(vjps, acc):
        """One microbatch's backward; adds d(loss_i)/dθ into ``acc``."""
        vjp0, mvjps, vjpL = vjps
        gL, g_h, _ = vjpL((one, aux_ct))
        for s in reversed(range(1, S - 1)):
            g_s, g_h = mvjps[s - 1]((g_h, aux_ct))
            _acc_stage(acc, g_s, stages, s)
        g0, _ = vjp0((g_h, aux_ct))
        _acc_stage(acc, gL, stages, S - 1)
        _acc_stage(acc, g0, stages, 0)
        return acc

    @jax.named_scope(ACCUMULATE)
    def _acc_stage(acc, g, st, s):
        b0, b1 = st.unit_range(s)
        acc["stack"] = jax.tree.map(
            lambda a, x: a.at[b0:b1].add(x.astype(jnp.float32)),
            acc["stack"], g["stack"])
        for k in ("embed", "final_norm", "lm_head"):
            if k in g:
                acc[k] = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc[k], g[k])

    def _step(params, opt_state, batch):
        b = batch["tokens"].shape[0]
        if b % M:
            raise ValueError(
                f"global batch {b} not divisible by microbatches {M}")
        mbs = jax.tree.map(
            lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]), batch)
        sp = [stage_params(params, s) for s in range(S)]
        # f32 accumulator derived FROM params so the param sharding
        # propagates (see the unstaged path's note)
        with jax.named_scope(ACCUMULATE):
            acc = jax.tree.map(lambda x: (x * 0).astype(jnp.float32), params)

        def mb_i(i):
            return jax.tree.map(lambda x: x[i], mbs)

        # --- 1F1B: warmup forwards, steady alternation, cooldown ------- #
        in_flight, mets = [], []
        warm = min(S - 1, M)
        for i in range(warm):
            vjps, met = forward_mb(sp, mb_i(i))
            in_flight.append(vjps)
            mets.append(met)
        nxt = warm
        while in_flight:
            acc = backward_mb(in_flight.pop(0), acc)
            if nxt < M:
                vjps, met = forward_mb(sp, mb_i(nxt))
                in_flight.append(vjps)
                mets.append(met)
                nxt += 1

        with jax.named_scope(ACCUMULATE):
            grads = jax.tree.map(lambda g: g / M, acc)
        metrics = {k: jnp.mean(jnp.stack([m[k] for m in mets]))
                   for k in mets[0]}
        new_params, new_state, om = adamw_update(
            params, grads, opt_state, cfg.optimizer)
        metrics.update(om)
        return new_params, new_state, metrics

    def train_step(params, opt_state, batch):
        with kernel_dispatch.force_backend(cfg.kernel_backend):
            return _step(params, opt_state, batch)

    return train_step
