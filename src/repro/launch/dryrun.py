import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline terms from the compiled artifact.

The lines above MUST precede every other import (jax locks the device
count at first init); do not move them.

Per cell:
  1. export the computation graph, run the strategy search (or a baseline),
  2. realize the strategy as shardings (plan -> PartitionSpecs),
  3. ``jax.jit(step, in_shardings=..., ...).lower(**abstract inputs)`` and
     ``.compile()`` — ShapeDtypeStructs only, nothing is allocated,
  4. record ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs/bytes) and the per-chip collective bytes parsed from the
     compiled HLO, to ``results/dryrun/<cell>.json``.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k \
      --mesh single --strategy search
  python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import CostModel, find_strategy, BASELINES
from repro.core.device import (ICI_BW, TPU_V5E_HBM_BW, TPU_V5E_HBM_BYTES,
                               TPU_V5E_PEAK_FLOPS)
from repro.core.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, production_mesh_spec
from repro.models import model_module, strategy_to_plan, uniform_plan
from repro.models.arch import SHAPES
from repro.models.graph_export import export_graph
from repro.optim import adamw_init
from repro.plans import (adamw_shardings, batch_pspecs, cache_pspecs,
                         dominant_unit_plan, param_pspecs, to_shardings)
from repro.serve import make_serve_fns
from repro.train import TrainConfig, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# TPU v5e roofline constants (per chip) — raw peaks: the compiled-HLO
# roofline reads the hardware ceiling, not the derated cost-model rates
PEAK_FLOPS = TPU_V5E_PEAK_FLOPS
HBM_BW = TPU_V5E_HBM_BW
LINK_BW = ICI_BW
HBM_BYTES = TPU_V5E_HBM_BYTES

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "pred": 1, "s8": 1,
                "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}


def collective_bytes(hlo_text: str) -> dict:
    """Per-chip bytes sent, per collective kind (operand-size convention)."""
    out: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, shape_s, kind = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        if shape_s:
            for d in shape_s.split(","):
                elems *= int(d)
        out_bytes = elems * _DTYPE_BYTES[dtype]
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        if kind == "all-gather":
            operand = out_bytes / max(1, g)
        elif kind == "reduce-scatter":
            operand = out_bytes * g
        else:
            operand = out_bytes
        out[kind] = out.get(kind, 0.0) + operand
        counts[kind] = counts.get(kind, 0) + 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def input_specs(arch, shape, *, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    B, S = shape.global_batch, shape.seq_len
    if arch.enc_layers:
        Se = min(4096, max(16, S // 2)) if shape.kind == "decode" else S // 2
        Sd = S if shape.kind == "decode" else S // 2
        batch = {"frames": jax.ShapeDtypeStruct((B, Se, arch.d_model), dtype),
                 "tokens": jax.ShapeDtypeStruct((B, Sd), jnp.int32)}
        return {"batch": batch, "dec_len": Sd, "enc_len": Se}
    if arch.frontend:
        F = arch.frontend_tokens
        batch = {"tokens": jax.ShapeDtypeStruct((B, S - F), jnp.int32),
                 "frontend": jax.ShapeDtypeStruct((B, F, arch.d_model), dtype)}
        return {"batch": batch, "dec_len": S, "enc_len": 0}
    return {"batch": {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)},
            "dec_len": S, "enc_len": 0}


def build_strategy(arch, shape, mesh_spec, strategy_name: str, *,
                   num_stages: int = 0, microbatches: int = 8,
                   profile=None):
    """Search (or apply a baseline to) one cell's graph; ``num_stages``
    routes a train-kind search through the two-level pipeline search
    (>1 forces the count, <0 auto-searches); ``profile`` (a measured
    DeviceProfile) calibrates the cost model first.  Returns
    (graph, strategy, comm bytes, StagedStrategy | None)."""
    graph = export_graph(arch, shape)
    cm = CostModel.from_profile(profile, mesh_spec, phase=shape.kind)
    mesh_spec = cm.mesh
    staged = None
    if strategy_name == "search":
        if num_stages not in (0, 1) and shape.kind == "train":
            from repro.core.stages import find_staged_strategy
            staged = find_staged_strategy(
                graph, mesh_spec, n_units=arch.n_units, phase=shape.kind,
                num_stages=num_stages if num_stages > 1 else None,
                max_stages=arch.n_units if num_stages < 0 else None,
                microbatches=microbatches, profile=profile)
            strat = staged.strategy
            strat.cost = staged.cost
        else:
            strat = find_strategy(graph, mesh_spec, phase=shape.kind,
                                  profile=profile)
    else:
        strat = BASELINES[strategy_name](graph, mesh_spec)
        strat.cost = cm.total_time(graph, strat)
    comm = cm.comm_bytes(graph, strat)
    return graph, strat, comm, staged


def dryrun_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
                strategy_name: str = "search", dtype=jnp.bfloat16,
                train_cfg: TrainConfig | None = None, plan_override=None,
                save: bool = True, tag: str = "",
                num_stages: int = 0, microbatches: int = 8,
                show_plan: bool = False, profile_path: str = "") -> dict:
    arch = configs.get(arch_name)
    shape = SHAPES[shape_name]
    mesh_tag = "multi" if multi_pod else "single"
    cell_id = f"{arch_name}__{shape_name}__{mesh_tag}__{strategy_name}{tag}"
    skip = arch.skip_reason(shape)
    if skip:
        return {"cell": cell_id, "status": "skipped", "reason": skip}

    profile = None
    if profile_path:
        from repro.profiling import load_profile
        profile = load_profile(profile_path)
        print(f"dryrun: device profile {profile_path} "
              f"[{profile.device_kind}] calibrates the cost model")

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_spec = production_mesh_spec(multi_pod=multi_pod)
    graph, strat, model_comm, staged = build_strategy(
        arch, shape, mesh_spec, strategy_name,
        num_stages=num_stages, microbatches=microbatches, profile=profile)
    calib = None
    if profile is not None:
        # predicted-vs-measured per layer: the calibrated roofline against
        # a timed equivalent of each layer's per-device work on this host
        from repro.profiling import format_layer_report, layer_report
        cm_cal = CostModel.from_profile(profile, mesh_spec,
                                        phase=shape.kind)
        calib = layer_report(graph, cm_cal, strat)
        print(format_layer_report(calib))
    if show_plan or staged is not None:
        # per-layer table, and next to it the stage assignment + pipeline
        # cost breakdown when the search was staged
        print(strat.describe(graph, mesh_spec))
        if staged is not None:
            pipe = staged.meta.get("pipeline", {})
            print(f"stages [{shape.kind}]: {staged.stages.describe()} "
                  f"on axis {staged.meta.get('factored_axis')!r}")
            for s in range(staged.stages.num_stages):
                b0, b1 = staged.stages.unit_range(s)
                print(f"  stage {s}: units [{b0},{b1}) "
                      f"cost={staged.stage_costs[s]:.6f}s")
            print(f"  pipeline: total={staged.cost:.6f}s "
                  f"bubble={staged.bubble_frac:.3f} "
                  f"interstage={staged.interstage_bytes:.0f}B "
                  f"xfer={pipe.get('xfer_s', 0.0):.6f}s")
    plan = plan_override or strategy_to_plan(strat, arch)
    mod = model_module(arch)

    # abstract params via eval_shape: nothing is allocated
    init = (mod.init_encdec if arch.enc_layers else mod.init_lm)
    params_abs = jax.eval_shape(
        lambda k: init(k, arch, dtype), jax.random.PRNGKey(0))
    p_specs = param_pspecs(params_abs, arch, plan)
    p_sh = to_shardings(p_specs, mesh, like=params_abs)
    specs = input_specs(arch, shape, dtype=dtype)
    batch_abs = specs["batch"]
    b_sh = to_shardings(batch_pspecs(batch_abs, plan), mesh,
                        like=batch_abs)

    if train_cfg is None:
        # gradient-accumulation heuristic: big-width models microbatch the
        # 1M-token global batch (the standard 100B+-scale recipe); the
        # grad-accum buffers stay params-sharded so only activations shrink.
        mb = 1 if arch.d_model <= 2048 else (4 if arch.d_model <= 4096 else 16)
        train_cfg = TrainConfig(microbatches=mb)
    with use_mesh(mesh):
        if shape.kind == "train":
            opt_abs = jax.eval_shape(adamw_init, params_abs)
            o_sh = adamw_shardings(p_sh, mesh)
            step = make_train_step(arch, plan, train_cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
        else:
            prefill_fn, decode_fn = make_serve_fns(
                arch, plan, q_chunk=train_cfg.q_chunk)
            cache_kw = ({"enc_len": specs["enc_len"]}
                        if arch.enc_layers else {})
            cache_abs = jax.eval_shape(
                lambda: (mod.init_cache(arch, shape.global_batch,
                                        specs["dec_len"], dtype, **cache_kw)))
            c_sh = to_shardings(cache_pspecs(cache_abs, arch, plan), mesh,
                                like=cache_abs)
            if shape.kind == "prefill":
                jitted = jax.jit(prefill_fn,
                                 in_shardings=(p_sh, b_sh, c_sh),
                                 out_shardings=(None, c_sh),
                                 donate_argnums=(2,))
                lowered = jitted.lower(params_abs, batch_abs, cache_abs)
            else:  # decode: one new token against a full cache
                tok_abs = jax.ShapeDtypeStruct(
                    (shape.global_batch, 1), jnp.int32)
                t_sh = to_shardings(batch_pspecs({"t": tok_abs}, plan),
                                    mesh, like={"t": tok_abs})["t"]
                jitted = jax.jit(
                    decode_fn, in_shardings=(p_sh, t_sh, c_sh, None),
                    out_shardings=(None, c_sh), donate_argnums=(2,))
                lowered = jitted.lower(params_abs, tok_abs, cache_abs,
                                       jnp.int32(0))
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)

    # trip-count-aware accounting (cost_analysis counts while bodies once;
    # scanned-layer models would be understated ~n_layers x).
    from repro.launch.hlo_analysis import analyze
    deep = analyze(hlo)

    n_chips = mesh.devices.size
    if isinstance(cost, list):  # CPU backend wraps the dict in a list
        cost = cost[0] if cost else {}
    flops_raw = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_raw = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    flops = max(flops_raw, deep["flops"])
    bytes_acc = max(bytes_raw, deep["hbm_bytes"])
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_acc / HBM_BW
    coll_s = deep["collective_bytes"]["total"] / LINK_BW
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    result = {
        "cell": cell_id,
        "status": "ok",
        "arch": arch_name,
        "shape": shape_name,
        "mesh": mesh_tag,
        "strategy": strategy_name,
        "n_chips": n_chips,
        "search_cost_s": strat.cost,
        "search_seconds": strat.meta.get("search_seconds"),
        "device_profile": strat.meta.get("device_profile"),
        "calibration": (None if calib is None else {
            "median_rel_error": calib["median_rel_error"],
            "max_rel_error": calib["max_rel_error"],
            "num_layers": calib["num_layers"],
        }),
        "model_comm_bytes": model_comm,
        "pipeline": (None if staged is None else {
            "stage_count": staged.stages.num_stages,
            "boundaries": list(staged.stages.boundaries),
            "microbatches": staged.stages.microbatches,
            "bubble_frac": staged.bubble_frac,
            "interstage_bytes": staged.interstage_bytes,
            "stage_costs_s": list(staged.stage_costs),
            "stage_search_seconds": staged.meta.get("stage_search_seconds"),
        }),
        "hbm": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": per_dev_bytes,
            "fits_16GiB": bool(per_dev_bytes < HBM_BYTES),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "xla_cost_analysis": {"flops": flops_raw, "bytes": bytes_raw},
        "collective_bytes_per_device": deep["collective_bytes"],
        "collective_counts": colls["counts"],
        "collective_exec_counts": deep["collective_exec_counts"],
        "top_collectives": deep.get("top_collectives", []),
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": coll_s,
            "dominant": max(
                [("compute", compute_s), ("memory", memory_s),
                 ("collective", coll_s)], key=lambda kv: kv[1])[0],
        },
        "wall_seconds": time.time() - t0,
    }
    if save:
        RESULTS.mkdir(parents=True, exist_ok=True)
        with open(RESULTS / f"{cell_id}.json", "w") as f:
            json.dump(result, f, indent=1)
    return result


def iter_cells():
    for arch_name in configs.ALL_ARCHS:
        for shape_name in SHAPES:
            yield arch_name, shape_name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default="search",
                    choices=["search", "data", "model", "owt"])
    ap.add_argument("--stages", type=int, default=0,
                    help="pipeline-stage the train-kind search: >1 forces "
                         "that stage count, -1 auto-searches; prints the "
                         "stage assignment and pipeline cost breakdown "
                         "next to the per-layer table")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="1F1B microbatch count M the pipeline is priced "
                         "with (used with --stages)")
    ap.add_argument("--show-plan", action="store_true",
                    help="print the searched per-layer table for every cell")
    ap.add_argument("--device-profile", default="",
                    help="measured DeviceProfile JSON (launch.profile); "
                         "calibrates the search cost model and prints a "
                         "per-layer predicted-vs-measured report")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = (list(iter_cells()) if args.all
             else [(args.arch, args.shape)])
    failures = 0
    for arch_name, shape_name in cells:
        for mp in meshes:
            tagname = (f"{arch_name}__{shape_name}__"
                       f"{'multi' if mp else 'single'}__{args.strategy}")
            out = RESULTS / f"{tagname}.json"
            if args.skip_existing and out.exists():
                print(f"[skip existing] {tagname}")
                continue
            try:
                r = dryrun_cell(arch_name, shape_name, multi_pod=mp,
                                strategy_name=args.strategy,
                                num_stages=args.stages,
                                microbatches=args.microbatches,
                                show_plan=args.show_plan,
                                profile_path=args.device_profile)
                if r["status"] == "skipped":
                    print(f"[SKIPPED] {tagname}: {r['reason']}")
                    RESULTS.mkdir(parents=True, exist_ok=True)
                    with open(out, "w") as f:
                        json.dump(r, f, indent=1)
                else:
                    rf = r["roofline"]
                    print(f"[OK] {tagname}: mem/dev="
                          f"{r['hbm']['per_device_total']/2**30:.2f}GiB "
                          f"fits={r['hbm']['fits_16GiB']} "
                          f"compute={rf['compute_s']*1e3:.2f}ms "
                          f"memory={rf['memory_s']*1e3:.2f}ms "
                          f"coll={rf['collective_s']*1e3:.2f}ms "
                          f"dominant={rf['dominant']} "
                          f"wall={r['wall_seconds']:.0f}s")
            except Exception:
                failures += 1
                print(f"[FAIL] {tagname}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
