"""End-to-end training driver.

Runs the full stack on whatever devices exist: search a strategy for the
actual mesh (or take a baseline), realize it, build the train step, stream
the synthetic pipeline, checkpoint periodically, and resume after failures
(``--resume`` restores the newest complete checkpoint and continues the
data stream deterministically from the restored step).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 200 --batch 8 --seq 256 --width 256 --depth 8

Reduced dims (``--width/--depth/--vocab``) scale the assigned arch down for
single-host runs; omit them on a real pod.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import compat, configs
from repro.checkpoint import CheckpointManager
from repro.core.sharding import use_mesh
from repro.data import make_dataset
from repro.kernels import dispatch as kernel_dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import host_mesh
from repro.models import model_module
from repro.models.arch import ShapeSpec
from repro.optim import AdamWConfig, adamw_init
from repro.plans import (adamw_shardings, param_pspecs, resolve_plan,
                         to_shardings)
from repro.train import TrainConfig, make_train_step


def reduced_arch(arch, width, depth, vocab, experts):
    kw = {}
    if width:
        head = max(1, arch.n_heads)
        kw.update(d_model=width, d_ff=width * 4,
                  moe_d_ff=width * 4 if arch.moe_d_ff else 0,
                  head_dim=0)
        if width % arch.n_heads != 0:
            kw.update(n_heads=8, n_kv_heads=min(8, arch.n_kv_heads))
    if depth:
        period = arch.period
        kw["n_layers"] = max(period, (depth // period) * period)
        if arch.enc_layers:
            kw["enc_layers"] = depth
    if vocab:
        kw["vocab"] = vocab
    if experts and arch.n_experts:
        kw.update(n_experts=experts, top_k=min(arch.top_k, experts))
    return dataclasses.replace(arch, **kw) if kw else arch


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--width", type=int, default=0)
    ap.add_argument("--depth", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--experts", type=int, default=0)
    ap.add_argument("--strategy", default="search",
                    choices=["search", "searched", "data", "model", "owt",
                             "uniform", "none"])
    ap.add_argument("--pipeline-stages", type=int, default=0,
                    help="pipeline the train phase over this many stages "
                         "(searched two-level; needs --strategy search and "
                         "a device count divisible by it); 0/1 = no "
                         "pipelining, -1 = auto-search the stage count")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="1F1B microbatch count M when pipelining "
                         "(--batch must divide by it)")
    ap.add_argument("--plan", default="",
                    help="load a ParallelPlan JSON (the train phase is "
                         "used); overrides --strategy, refuses an arch "
                         "mismatch")
    ap.add_argument("--save-plan", default="",
                    help="write the plan (searched or baseline) to this "
                         "JSON path; reload with --plan here or on the "
                         "serve driver")
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--overfit-batches", type=int, default=0,
                    help="cycle the first N batches of the stream instead "
                         "of fresh ones (0 = off): a sanity run in which the "
                         "loss must fall, whatever the data's entropy")
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--kernel-backend", default="",
                    help="force a kernel dispatch backend "
                         "(pallas|interpret|xla|ref) for every op — "
                         "attention, wkv6, mamba_scan, moe_dispatch_combine;"
                         " default auto")
    ap.add_argument("--device-profile", default="",
                    help="measured DeviceProfile JSON (launch.profile); "
                         "calibrates the plan search's cost model to this "
                         "host instead of the analytic constants")
    return ap


def weight_shards(params) -> list[dict]:
    """Per matmul weight (ndim >= 2): its global shape, the distinct
    shard shapes its addressable shards hold, and over how many devices —
    the proof that a plan's sharding is physical, not just annotated."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if leaf.ndim < 2:
            continue
        shards = leaf.addressable_shards
        out.append({"name": jax.tree_util.keystr(path),
                    "shape": tuple(leaf.shape),
                    "shard_shapes": sorted({tuple(s.data.shape)
                                            for s in shards}),
                    "devices": len({s.device for s in shards})})
    return out


def kernels_line(pplan) -> str:
    """The plan beside the kernels its step traced shard-mapped under the
    mesh and those passed over there (spmd-unsafe, split they cannot
    take)."""
    def said(counts):
        return ", ".join(f"{op}/{b} x{n}" for (op, b), n in
                         sorted(counts.items())) or "none"
    return (f"train: plan {pplan.strategy_name}; shard-mapped kernels "
            f"{said(kernel_dispatch.spmd_maps())}; passed over "
            f"{said(kernel_dispatch.spmd_skips())}")


def memory_line(pplan, compiled) -> str:
    """The plan search's bytes a chip beside what XLA compiled the step
    to hold: its arguments and its temporaries."""
    counted = pplan.meta.get("phases", {}).get("train", {}).get(
        "device_bytes")
    said = "nothing" if counted is None else f"{counted / 1e9:.3f} GB"
    mem = compiled.memory_analysis()
    if mem is None:
        return f"train: memory a chip: search counted {said}"
    arg, tmp = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    return (f"train: memory a chip: search counted {said}; compiled step "
            f"arguments {arg / 1e9:.3f} GB + temporaries {tmp / 1e9:.3f} GB "
            f"= {(arg + tmp) / 1e9:.3f} GB")


def run(argv=None) -> dict:
    """Search (or load) a plan, train, and return what happened:
    per-step ``history`` (every step is logged), ``compile_s`` (the
    first step, trace + compile + run), ``step_s`` (mean of the later
    steps, each waited on), the plan name and ``weight_shards`` of the
    final parameters."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    arch = reduced_arch(configs.get(args.arch), args.width, args.depth,
                        args.vocab, args.experts)
    shape = ShapeSpec("custom", args.seq, args.batch, "train")
    n_dev = jax.device_count()
    mesh, mesh_spec = host_mesh(n_dev)

    name = {"search": "searched", "none": "uniform"}.get(
        args.strategy, args.strategy)
    pplan = resolve_plan(
        arch, mesh_spec if n_dev > 1 else None, phases=("train",),
        plan_path=args.plan, strategy=name, save_plan=args.save_plan,
        train_seq=args.seq, train_batch=args.batch,
        train_stages=args.pipeline_stages,
        train_microbatches=args.microbatches,
        profile_path=args.device_profile)
    plan = pplan.plan_for("train")
    train_stages = pplan.stage_for("train")
    if train_stages.num_stages > 1:
        # the execution mesh factors the searched stage axis out of the
        # device grid so the stage-sharded stack PartitionSpecs resolve;
        # a non-dividing device count drops the axis (replicated stack)
        S = train_stages.num_stages
        if n_dev % S == 0 and n_dev >= S:
            mesh = compat.make_mesh((S, n_dev // S, 1),
                                    (train_stages.mesh_axis, "data", "model"))
        print(f"train: pipeline S={S} M={train_stages.microbatches} "
              f"boundaries={train_stages.boundaries}")

    mod = model_module(arch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                          total_steps=args.steps)
    tcfg = TrainConfig(optimizer=opt_cfg, q_chunk=256, time_chunk=32,
                       remat=True, kernel_backend=args.kernel_backend or None)
    step_fn = make_train_step(
        arch, pplan if train_stages.num_stages > 1 else plan, tcfg)
    ds = make_dataset(arch, shape)

    ckpt = (CheckpointManager(args.ckpt_dir)
            if args.ckpt_every or args.resume else None)
    init = mod.init_encdec if arch.enc_layers else mod.init_lm
    params = init(jax.random.PRNGKey(0), arch, jnp.float32)
    opt_state = adamw_init(params)
    start_step = 0
    if args.resume:
        like = {"params": params, "opt": opt_state}
        step, state = ckpt.restore_latest(like)
        if step is not None:
            params, opt_state = state["params"], state["opt"]
            start_step = step
            print(f"resumed from step {step}")

    p_sh = to_shardings(param_pspecs(params, arch, plan, stages=train_stages),
                        mesh, like=params)
    # ZeRO-1 moments, as the plan search counts them; pinning the step's
    # output shardings to the input ones keeps step 2 from recompiling
    o_sh = adamw_shardings(p_sh, mesh)
    params = jax.device_put(params, p_sh)
    opt_state = jax.device_put(opt_state, o_sh)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1),
                       out_shardings=(p_sh, o_sh, None))

    history, step_times = [], []
    compiled = None
    with use_mesh(mesh):
        for step in range(start_step, args.steps):
            data_step = (step % args.overfit_batches if args.overfit_batches
                         else step)
            batch = jax.tree.map(jnp.asarray, ds.batch_at(data_step))
            t0 = time.perf_counter()
            if compiled is None:
                kernel_dispatch.reset_selection_counts()
                compiled = jit_step.lower(params, opt_state, batch).compile()
                print(memory_line(pplan, compiled))
                print(kernels_line(pplan))
            params, opt_state, metrics = compiled(params, opt_state, batch)
            m = {k: float(v) for k, v in metrics.items()}   # waits
            step_times.append(time.perf_counter() - t0)
            history.append({"step": step, **m})
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss={m['loss']:.4f} "
                      f"nll={m['nll']:.4f} acc={m['accuracy']:.3f} "
                      f"gnorm={m['grad_norm']:.2f} "
                      f"step_s={step_times[-1]:.4f}")
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if args.ckpt_every:
        ckpt.save(args.steps, {"params": params, "opt": opt_state})
    if args.metrics_out:
        out = Path(args.metrics_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(history, indent=1))
    steady = step_times[1:]
    return {"arch": arch.name, "vocab": arch.vocab,
            "plan": pplan.strategy_name,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "tokens_per_step": shape.tokens, "history": history,
            "compile_s": step_times[0] if step_times else 0.0,
            "step_s": sum(steady) / len(steady) if steady else 0.0,
            "weight_shards": weight_shards(params)}


def main() -> None:
    res = run()
    hist = res["history"]
    if not hist:
        return
    first, last = hist[0]["nll"], hist[-1]["nll"]
    print(f"compile {res['compile_s']:.2f} s (first step), steady "
          f"{res['step_s']:.4f} s/step")
    print(f"nll {first:.4f} -> {last:.4f}")
    if not all(math.isfinite(h["loss"]) for h in hist):
        raise SystemExit("non-finite loss")
    if len(hist) > 1 and not last < first:
        raise SystemExit(f"nll did not fall: {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
