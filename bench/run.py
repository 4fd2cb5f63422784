#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the last line of standard output is one JSON object
holding the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window, with
``breakdown``.  ``correct`` says whether the plain reference agrees with
what the timed path produced; the numbers compared, each with its limit,
are the last lines of standard error and the last key of the JSON.

Without a TPU, with fewer chips than the cell asks for, or without the
program's ``src/`` beside ``bench/``, it exits non-zero and prints no
result.  ``--control`` also reads the control (the reference one
precision step below the configuration's) and the planted faults;
``--rate`` overrides a serving mix's arrival rate (the knee sweep).
Neither is used by a benchmark run.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import importlib     # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the control and the planted faults")
    ap.add_argument("--rate", type=float, default=None,
                    help="serving: requests/s in place of the mix's rate")
    return ap.parse_args(argv)


def load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell, out: dict, device: dict) -> tuple[dict, dict, dict]:
    """Read the trace once, then each per-layer metric of the cell; a
    reader that finds nothing returns None and its metric is left out."""
    from bench.core import trace as tr
    from bench.core.peaks import peaks_for

    rec = out["record"]
    rec["peaks"] = peaks_for(device["kind"])
    rec["trace"] = (tr.load(rec["trace_dir"], out["spans"])
                    if rec.get("trace_dir") else None)
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    extra, breakdown = {}, {}
    t = rec["trace"]
    if t is not None and t.device_ops:
        lo, hi = t.window
        busy = [tr.busy_ns(ops, lo, hi) for ops in t.device_ops]
        extra = {"busy_s": sum(busy) / len(busy) / 1e9,
                 "window_s": (hi - lo) / 1e9}
        ops = [o for d in t.device_ops for o in d]
        breakdown = {"device_ops": tr.top_ops(ops),
                     "idle_gaps": tr.idle_gaps(t.device_ops[0], t.host_spans,
                                               lo, hi)}
    return metrics, extra, breakdown


def judge_controls(control: dict, limits: dict) -> dict:
    """The control's readings and each planted fault's (``<group>_<number>``)
    judged as the program's are, against the cell's limits: each group
    gains ``<group>_correct``, which has to come out false."""
    from bench.core import checks

    out = dict(control)
    for group in sorted({k[:-len(n) - 1] for k in control for n in limits
                         if k.endswith("_" + n)}):
        readings = {n: control[f"{group}_{n}"] for n in limits
                    if f"{group}_{n}" in control}
        out[f"{group}_correct"] = checks.judge(readings, limits)[0]
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is missing ({ROOT / 'src' / 'repro'})",
              file=sys.stderr)
        return 2
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))

    from bench.core.spec import load_cell
    cell = load_cell(args.workload, ROOT)

    import jax

    from bench.core.runtime import CompileCount, enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX sees {devs[0].platform}); this "
              f"benchmark measures the chip and never falls back",
              file=sys.stderr)
        return 1
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    return run(cell, args, enable_compile_cache(), CompileCount())


def run(cell, args, cache_dir, compiles) -> int:
    """Drive the cell and print the result; callable without a chip."""
    from bench.core import checks
    from bench.core.runtime import device_info

    device = device_info(cell.chips)
    args.compiles, args.fault = compiles, getattr(args, "fault", None)
    print(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} on {device['kind']} x{device['count']}; "
          f"compile cache {cache_dir}", flush=True)
    driver = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    out = driver.run(cell, args, T_START)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    for k, v in out["record"]["counters"].items():
        print(f"[counter] {k}: {v}", flush=True)
    print(f"[compile] {dict(compiles.counts)} (window: "
          f"{compiles.counts['compiles_window']} compilations)", flush=True)

    breakdown = {}
    try:
        if args.trace:
            metrics, extra, breakdown = per_layer(cell, out, device)
            device.update(extra)
        else:
            metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
    finally:
        prof = out["record"].get("profiler")
        if prof is not None:
            prof.cleanup()
    for k, v in out["metrics"].items():
        print(f"[metric] {k}: {v}", flush=True)

    limits = checks.load_limits(cell.name)
    correct, compared = checks.judge(out["readings"], limits)
    if compiles.counts["compiles_window"]:
        print("bench: programs compiled inside the window", file=sys.stderr)
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if out.get("control"):
        result["control"] = judge_controls(out["control"], limits)
        for k, v in result["control"].items():
            print(f"[control] {k}: {v!r}", file=sys.stderr)
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    bad = [k for k, v in metrics.items()
           if not math.isfinite(v["value"])]
    if bad:
        print(f"bench: non-finite metrics {bad}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
