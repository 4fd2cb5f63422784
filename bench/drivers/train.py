"""Training: the program's jitted train step under its resolved plan.

Set-up makes the weights from the seed, resolves the plan
(``resolve_plan``), builds the step (``make_train_step``), lays the
parameters and AdamW state out (``param_pspecs``, ``to_shardings``,
``adamw_shardings``) and jits the step with donation and pinned output
shardings, as ``launch/train.run`` does.  That one object then runs the
first three steps, whose loss, first gradient and update the
configuration family's reference checks over the cell's chips, and goes
on into the window: fresh batches from the seed, every step waited on.
"""

from __future__ import annotations

import gc
import time

from bench.core import checks, weights
from bench.core.runtime import (Profiler, SetupParts, memory_peak_bytes,
                                 span)
from bench.core.spec import program_arch
from bench.reference import family

SPANS = ("train.batch", "train.step", "train.fetch")
FAULTS = ("state", "half")
CHECKED_STEPS = 3


def run(cell, opts, t_start: float) -> dict:
    import jax

    from repro.core.sharding import use_mesh
    from repro.kernels import dispatch
    from repro.launch.mesh import host_mesh
    from repro.optim import AdamWConfig, adamw_init
    from repro.plans import param_pspecs, resolve_plan, to_shardings
    from repro.train import TrainConfig, make_train_step

    setup = SetupParts(t_start)
    setup("start")
    cfg, tr = cell.config, cell.traffic
    B, S, V = tr["batch"], tr["seq"], cfg["vocab_size"]
    arch = program_arch(cfg)
    n_dev = cell.chips
    mesh, mesh_spec = host_mesh(n_dev)
    t = time.perf_counter()
    pplan = resolve_plan(arch, mesh_spec if n_dev > 1 else None,
                         phases=("train",), strategy=tr["strategy"],
                         train_seq=S, train_batch=B)
    plan_s = time.perf_counter() - t
    setup("plan")
    plan = pplan.plan_for("train")
    opt_cfg = AdamWConfig(**tr["optimizer"])
    step_fn = make_train_step(arch, plan, TrainConfig(
        optimizer=opt_cfg, q_chunk=tr["q_chunk"], loss_chunk=tr["loss_chunk"],
        microbatches=tr.get("microbatches", 1), remat=True))
    if opts.fault == "state":
        inner = step_fn
        step_fn = lambda p, o, b: (p,) + tuple(inner(p, o, b)[1:])
    elif opts.fault == "half":
        inner = step_fn
        step_fn = lambda p, o, b: inner(
            p, o, {"tokens": b["tokens"][:b["tokens"].shape[0] // 2]})
    elif opts.fault is not None:
        raise ValueError(f"unknown training fault {opts.fault!r}; have {FAULTS}")

    params = weights.make_params(cfg, opts.seed)
    weights.check_layout(params, arch)
    p_sh = to_shardings(param_pspecs(params, arch, plan), mesh, like=params)
    o_sh = adamw_shardings(p_sh, mesh)
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(adamw_init(params), o_sh)
    step = jax.jit(step_fn, donate_argnums=(0, 1),
                   out_shardings=(p_sh, o_sh, None))
    jax.block_until_ready((params, opt))
    setup("weights")
    dispatch.reset_selection_counts()

    def batch_at(i):
        return weights.make_batch(opts.seed, i, B, S, V)

    # ---- the first steps, checked later against the reference -------- #
    losses, first_grad = [], None
    with use_mesh(mesh):
        for i in range(CHECKED_STEPS):
            params, opt, met = step(params, opt, batch_at(i))
            losses.append(float(met["loss"]))
            setup(f"checked_step_{i}")
            if i == 0:
                first_grad = weights.leaf_norms(opt["m"],
                                                1 / (1 - opt_cfg.b1))
        change = weights.leaf_change_norms(cfg, opts.seed, params)
        setup("change_norms")

        # ---- the window ----------------------------------------------- #
        prof = Profiler() if opts.trace else None
        trace_from = tr["trace_after_steps"]
        trace_to = trace_from + tr["trace_steps"]
        walls, traced, trace_dir = [], [], None
        t0 = time.perf_counter()
        opts.compiles.in_window = True
        i, t_end, t_last = CHECKED_STEPS, t0 + opts.seconds, t0
        try:
            while t_last < t_end:
                n = len(walls)
                if prof and n == trace_from:
                    prof.start()
                ts = time.perf_counter()
                with span("train.batch"):
                    batch = batch_at(i)
                with span("train.step"):
                    params, opt, met = step(params, opt, batch)
                with span("train.fetch"):
                    loss = float(met["loss"])
                t_last = time.perf_counter()
                walls.append(t_last - ts)
                if prof and prof.on:
                    traced.append(t_last - ts)
                    if n + 1 == trace_to:
                        trace_dir = prof.stop()
                i += 1
            if prof and prof.on:
                trace_dir = prof.stop()
        except BaseException:
            if prof:
                prof.cleanup()
            raise
        opts.compiles.in_window = False
    steps = len(walls)
    mem = memory_peak_bytes(n_dev)
    metrics = {"setup_s": t0 - t_start,
               "train_tokens_per_s": steps * B * S / (t_last - t0)}
    counters = {
        "plan": pplan.strategy_name, "plan_search_s": plan_s,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "steps_in_window": steps, "tokens_per_step": B * S,
        "last_loss": loss, "checked_losses": losses,
        "setup_parts_s": setup.parts,
        "selection_counts": {f"{o}/{b}": n for (o, b), n in
                             dispatch.selection_counts().items()},
        "spmd_skips": {f"{o}/{b}": n for (o, b), n in
                       dispatch.spmd_skips().items()},
    }
    record = {"cell": cell, "trace_dir": trace_dir, "traced_walls": traced,
              "tokens_per_step": B * S, "counters": counters,
              "profiler": prof}

    # ---- correctness: the reference follows the first three steps ----- #
    del params, opt, met, batch
    gc.collect()
    readings, control = _check(cfg, tr, opts, jax.devices()[:n_dev], losses,
                               first_grad, change)
    return {"attempted": steps, "failed": 0, "metrics": metrics,
            "readings": readings, "control": control, "record": record,
            "memory_peak_bytes": mem, "spans": SPANS}


def _readings(ref: dict, losses, first_grad, change) -> dict:
    skip = checks.negligible_leaves(ref["grad_norm"])
    grad_gap, grad_leaf = checks.leaf_gap(first_grad, ref["grad_norm"], skip)
    change_gap, change_leaf = checks.leaf_gap(change, ref["change_norm"], skip)
    print(f"[check] worst leaves: gradient {grad_leaf}, change {change_leaf};"
          f" not compared: {skip}", flush=True)
    return {"loss_gap": max(abs(a - b) for a, b in zip(losses, ref["loss"])),
            "grad_norm_gap": grad_gap, "update_norm_gap": change_gap}


def adamw_shardings(p_sh, mesh):
    """The AdamW state's shardings for parameters laid out as ``p_sh``:
    the program's own rule where it has one (``repro.plans.
    adamw_shardings(param_shardings, mesh)``), so a change of the
    program's optimizer layout reaches the benchmark; else the rule
    ``launch/train.run`` writes out, moments as their parameters and the
    step replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import plans

    own = getattr(plans, "adamw_shardings", None)
    if own is not None:
        return own(p_sh, mesh)
    return {"m": p_sh, "v": p_sh, "step": NamedSharding(mesh, PartitionSpec())}


def _check(cfg, tr, opts, devices, losses, first_grad, change):
    """Run the reference over the same first batches from the same
    weights, with its state split over the cell's ``devices``, and
    compare; with ``opts.control``, read the control (the reference in
    bfloat16) and the half-batch fault the same way."""
    import jax
    import jax.numpy as jnp

    B, S, V = tr["batch"], tr["seq"], cfg["vocab_size"]
    batches = [weights.make_batch(opts.seed, i, B, S, V)["tokens"]
               for i in range(CHECKED_STEPS)]
    change_fn = lambda p: weights.leaf_change_norms(cfg, opts.seed, p)

    def follow(dtype=jnp.float32, precision="highest", row_filter=None):
        # made inline, so the tree made on one chip is dropped once the
        # reference has split it over the cell's chips
        return family(cfg).train_readings(
            jax.tree.map(lambda x: x.astype(dtype),
                         weights.make_params(cfg, opts.seed)),
            batches, cfg, tr["optimizer"], cfg["z_loss_coef"], change_fn,
            devices=devices, rows_per_block=tr["reference_rows"],
            precision=precision, row_filter=row_filter)

    ref = follow()
    print(f"[check] losses program {losses} reference {ref['loss']}",
          flush=True)
    readings = _readings(ref, losses, first_grad, change)
    # neither the control nor a fault moves the loss of three steps on
    # uniform rows past what sound runs read, so it is printed, not
    # compared (PERF.md gives the readings)
    print(f"[check] loss_gap {readings.pop('loss_gap')!r} (not compared)",
          flush=True)
    control = None
    if opts.control:
        low = follow(jnp.bfloat16, "default")
        half = follow(row_filter=lambda b: b[:b.shape[0] // 2])
        control = {f"control_{k}": v for k, v in _readings(
            ref, low["loss"], low["grad_norm"], low["change_norm"]).items()}
        control.update({f"half_batch_{k}": v for k, v in _readings(
            ref, half["loss"], half["grad_norm"], half["change_norm"]).items()})
    return readings, control
