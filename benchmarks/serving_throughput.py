"""Serving throughput benchmark: continuous batching vs the static-batch
oracle — and the paged KV cache vs dense slot rows — on a Poisson
arrival trace with mixed prompt/output lengths.

    PYTHONPATH=src python -m benchmarks.serving_throughput --smoke \
        --out BENCH_serving.json

All modes run the *same* trace through the same engine machinery
(identical compiled fns — only the slot admission policy, cache layout
and prefill chunking differ), with all shapes warmed up before the
clock starts.  ``continuous`` runs chunked prefill (prompts ride the
mixed decode steps) on the paged cache (``--kv-block-size``, pool
auto-sized to the trace's worst-case request unless
``--kv-pool-blocks`` overrides); ``unchunked`` is the same engine with
``prefill_chunk_tokens=0`` (stall-the-world prefill — the chunking A/B
oracle); ``static`` is the lockstep admission baseline; a ``dense``
mode (continuous policy, per-slot ``max_len`` rows) is the memory
baseline.  Emits ``BENCH_serving.json`` — one point of the serving perf
trajectory: ``continuous_speedup`` < 1.0 and ``kv_bytes_reserved``
(paged mode) growing are the regression signals the CI bench gate
compares run over run; ``kv_reserved_frac`` is the paged/dense memory
ratio and ``paged_speedup`` the paged/dense throughput ratio (the paged
cache must win memory without losing tok/s).  Each mode reports
inter-token latency percentiles (``itl_p50_ms``/``itl_p95_ms``/
``itl_p99_ms`` — wall time of each engine step that had a decoding slot
at entry, so a stall-the-world prefill lands in the tail), and the
top-level ``chunked_itl_p99_ratio`` (continuous / unchunked p99) is the
headline chunking win the gate watches.

The trace can carry a shared-prefix segment (``--shared-prefix-len`` /
``--shared-frac``; on by default in ``--smoke``): those requests open
with one common system-prompt prefix, and the continuous mode's
copy-on-write prefix cache serves it from shared blocks — reported as
``prefix_hit_rate`` (requests that reused cached blocks) and
``prefill_tokens_saved`` (prompt tokens never re-prefilled), both gated
in CI alongside the other serving metrics.

``--kv-quant int8`` adds a ``continuous_int8`` mode — the same chunked
continuous engine on an int8 paged pool (per-row f32 scales riding the
block table) — and three top-level quantization metrics:
``quant_kv_reserved_frac`` (int8/fp bytes physically reserved = int8
payload + f32 scales over an f32 pool, 0.25 + 1/head_dim —
the smoke arch's head_dim 4 gives 0.50), ``quant_speedup`` (int8/fp
tok/s, informational) and ``quant_logit_agreement`` (teacher-forced max
absolute logit delta between a dense fp cache and the int8 paged pool —
pure quantization numerics, gated against a noise floor in CI).

``--train-stages N`` additionally prices a pipeline-staged *train* plan
(two-level search, :func:`repro.plans.search.search_phase_plan`) on a
synthetic 8-device mesh — pure cost model, no extra runtime — and
records ``stage_count`` (informational) and ``pipeline_bubble_frac``
(gated: the 1F1B bubble must not grow) in the report JSON.
"""

from __future__ import annotations

import argparse
import json
import time
from collections import deque
from pathlib import Path


def make_trace(n: int, rate: float, prompt_buckets, gen_range, vocab: int,
               seed: int = 0, shared_prefix_len: int = 0,
               shared_frac: float = 0.0) -> list[dict]:
    """A reproducible request trace.

    Arrival times are Poisson (exponential inter-arrival at ``rate``
    requests/s; ``rate <= 0`` means everything arrives at t=0), prompt
    lengths are drawn from ``prompt_buckets`` (a small set, so every
    prefill shape can be compiled up front), output lengths uniformly
    from ``gen_range`` (inclusive).  Returns dicts, not engine Requests —
    the trace is engine-agnostic.

    ``shared_prefix_len > 0`` adds the production shape prefix caching
    exists for: a ``shared_frac`` fraction of requests open with one
    common ``shared_prefix_len``-token prefix (a system prompt) followed
    by a unique tail — their bucket length keeps the tail when it
    reaches past the prefix, else the tail is a single token.  With
    ``shared_prefix_len=0`` (the default) the draw order is untouched,
    so existing seeds reproduce their exact pre-sharing traces.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    if rate > 0:
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    else:
        arrivals = np.zeros(n)
    plens = rng.choice(np.asarray(prompt_buckets), n)
    lo, hi = gen_range
    gens = rng.integers(lo, hi + 1, n)
    if shared_prefix_len > 0:
        shared = tuple(int(t)
                       for t in rng.integers(1, vocab, shared_prefix_len))
        is_shared = rng.random(n) < shared_frac
    else:
        shared, is_shared = (), np.zeros(n, bool)

    def prompt(i):
        if is_shared[i]:
            tail = max(1, int(plens[i]) - shared_prefix_len)
            return shared + tuple(int(t)
                                  for t in rng.integers(1, vocab, tail))
        return tuple(int(t) for t in rng.integers(1, vocab, plens[i]))

    return [{
        "uid": i,
        "arrival": float(arrivals[i]),
        "prompt": prompt(i),
        "max_new_tokens": int(gens[i]),
    } for i in range(n)]


def run_mode(engine, trace: list[dict]) -> dict:
    """Pace the trace's arrivals in real time through ``engine``; returns
    throughput/latency metrics.  The engine must already be warmed up on
    every prompt-length bucket in the trace."""
    import numpy as np

    from repro.serve import Request

    pending = deque(sorted(trace, key=lambda d: d["arrival"]))
    arrival = {d["uid"]: d["arrival"] for d in trace}
    finished: list[tuple] = []
    t0 = time.perf_counter()
    while pending or engine.busy:
        now = time.perf_counter() - t0
        while pending and pending[0]["arrival"] <= now:
            d = pending.popleft()
            engine.submit(Request(uid=d["uid"], prompt=d["prompt"],
                                  max_new_tokens=d["max_new_tokens"]))
        if engine.busy:
            for c in engine.step():
                finished.append((c, time.perf_counter() - t0))
        elif pending:
            time.sleep(min(max(pending[0]["arrival"] - now, 0.0), 0.01))
    wall = time.perf_counter() - t0

    out_tokens = sum(len(c.tokens) for c, _ in finished)
    lats = np.asarray([t - arrival[c.uid] for c, t in finished])
    s = engine.stats
    metrics = {
        "requests": len(finished),
        "wall_s": round(wall, 4),
        "output_tokens": int(out_tokens),
        "out_tok_per_s": round(out_tokens / max(wall, 1e-9), 2),
        "decode_steps": int(s["decode_steps"]),
        "decode_tok_per_s": round(
            s["decode_tokens"] / max(s["decode_s"], 1e-9), 2),
        # chunked engines have no separate prefill phase (prefill_s == 0,
        # the prompt tokens rode the mixed steps) — report 0, not inf
        "prefill_tok_per_s": (0.0 if s["prefill_s"] <= 0 else round(
            s["prefill_tokens"] / s["prefill_s"], 2)),
        "prefill_chunk_tokens": int(engine.chunk if engine.chunked else 0),
        "compile_s": round(s["compile_s"], 3),
        "latency_mean_s": round(float(lats.mean()), 4),
        "latency_p95_s": round(float(np.quantile(lats, 0.95)), 4),
        # memory truth: bytes physically reserved for KV and the paged
        # pool's allocation high-water mark (0 when dense / no KV)
        "kv_bytes_reserved": int(engine.kv_bytes_reserved),
        "kv_block_size": int(engine.block_size),
        "peak_blocks_in_use": int(engine.peak_blocks_in_use),
        # prefix-sharing truth: fraction of requests that reused cached
        # prompt blocks, and the prompt tokens never re-prefilled (both
        # 0 where sharing is off or inert — dense / unchunked modes)
        "prefix_hit_rate": round(float(engine.prefix_hit_rate), 4),
        "prefill_tokens_saved": int(engine.prefill_tokens_saved),
    }
    if engine.itl_samples:
        # wall time of each step that had a decoding slot at entry: a
        # stall-the-world prefill shows up as a fat p99, chunking's
        # whole point is to flatten it
        itl = np.asarray(engine.itl_samples) * 1e3
        for q in (50, 95, 99):
            metrics[f"itl_p{q}_ms"] = round(float(np.percentile(itl, q)), 3)
    return metrics


def quant_logit_probe(mod, params, arch, vocab: int, *, tokens: int = 48,
                      block_size: int = 16, seed: int = 3) -> float:
    """Teacher-forced numerics probe for the int8 paged pool: feed one
    random token stream through a dense fp cache and an int8 paged cache
    (identity block table, so both see the same logical KV) and return
    the max absolute logit delta over the stream.  This is the
    quantization error *alone* — no scheduling, no admission — which is
    what the CI gate can hold to a noise floor."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    toks = rng.integers(1, vocab, tokens)
    pages = -(-tokens // block_size)
    dense = mod.init_cache(arch, 1, pages * block_size, jnp.float32)
    quant = mod.init_paged_cache(arch, pages + 1, block_size, 1,
                                 jnp.float32, kv_quant="int8")
    bt = jnp.arange(1, pages + 1, dtype=jnp.int32)[None, :]
    delta = 0.0
    for i, t in enumerate(toks):
        tok = jnp.full((1, 1), int(t), jnp.int32)
        pos = jnp.full((1,), i, jnp.int32)
        ld, dense = mod.decode_step(params, tok, dense, pos, arch)
        lq, quant = mod.decode_step(params, tok, quant, pos, arch,
                                    block_tables=bt)
        delta = max(delta, float(jnp.max(jnp.abs(ld - lq))))
    return delta


def run_benchmark(*, arch_name: str, width: int, depth: int, vocab: int,
                  max_batch: int, n_requests: int, rate: float,
                  prompt_buckets, gen_range, out: str, seed: int = 0,
                  strategy: str = "uniform", plan_path: str = "",
                  save_plan: str = "", kv_block_size: int = 128,
                  kv_pool_blocks: int = 0, max_len: int = 0,
                  shared_prefix_len: int = 0,
                  shared_frac: float = 0.0,
                  train_stages: int = 0,
                  train_microbatches: int = 8,
                  kv_quant: str | None = None,
                  profile_path: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.core.sharding import use_mesh
    from repro.launch.mesh import host_mesh
    from repro.launch.serve import resolve_serve_plan
    from repro.launch.train import reduced_arch
    from repro.models import model_module
    from repro.serve import ServeConfig, ServeEngine, blocks_for_request

    arch = reduced_arch(configs.get(arch_name), width, depth, vocab, 4)
    max_len = max_len or (max(prompt_buckets) + gen_range[1])
    typical = min(max(prompt_buckets) + gen_range[1], max_len)
    # the chunk budget the continuous mode will run (ServeEngine's auto
    # default) — the plan prices decode as that mixed step
    chunk = min(2 * kv_block_size if kv_block_size else 256, max_len)
    n_dev = jax.device_count()
    mesh, mesh_spec = host_mesh(n_dev)
    plan = resolve_serve_plan(
        arch, mesh_spec if n_dev > 1 else None, plan_path=plan_path,
        strategy=strategy, prompt_len=max(prompt_buckets),
        max_batch=max_batch, max_len=max_len,
        kv_block_size=kv_block_size, typical_tokens=typical,
        prefill_chunk_tokens=chunk,
        shared_prefix_tokens=shared_prefix_len, save_plan=save_plan,
        profile_path=profile_path)
    kv_quant = None if kv_quant in (None, "none") else kv_quant
    plan_q = None
    if kv_quant and kv_block_size:
        # the int8 mode executes under a plan priced at the quantized
        # pool's cache-read width (and carrying kv_quant provenance in
        # its meta); the fp modes keep the fp-priced plan above
        plan_q = resolve_serve_plan(
            arch, mesh_spec if n_dev > 1 else None, plan_path=plan_path,
            strategy=strategy, prompt_len=max(prompt_buckets),
            max_batch=max_batch, max_len=max_len,
            kv_block_size=kv_block_size, typical_tokens=typical,
            prefill_chunk_tokens=chunk,
            shared_prefix_tokens=shared_prefix_len, kv_quant=kv_quant,
            profile_path=profile_path)
    mod = model_module(arch)
    params = mod.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)
    trace = make_trace(n_requests, rate, prompt_buckets, gen_range,
                       arch.vocab, seed, shared_prefix_len=shared_prefix_len,
                       shared_frac=shared_frac)
    buckets = sorted({len(d["prompt"]) for d in trace})
    if kv_block_size and not kv_pool_blocks:
        # auto pool: every slot simultaneously holding the trace's
        # worst-case request — the honest reservation, vs the dense
        # layout's max_batch * max_len
        kv_pool_blocks = max_batch * blocks_for_request(
            max(prompt_buckets), gen_range[1], max_len, kv_block_size)

    report = {
        "kind": "serving", "jax": jax.__version__,
        "backend": jax.default_backend(), "devices": n_dev,
        "arch": arch.name,
        "slots": max_batch, "requests": n_requests, "rate_rps": rate,
        "prompt_buckets": list(map(int, prompt_buckets)),
        "gen_range": list(map(int, gen_range)), "seed": seed,
        "max_len": int(max_len), "kv_block_size": int(kv_block_size),
        "kv_pool_blocks": int(kv_pool_blocks),
        "shared_prefix_len": int(shared_prefix_len),
        "shared_frac": float(shared_frac),
        "kv_quant": kv_quant or "none",
        # the plan the trace executed under, so the perf trajectory can
        # attribute throughput moves to strategy moves (plan-vs-uniform
        # speedup accumulates across CI runs)
        "plan": {
            "strategy": plan.strategy_name,
            "source": plan_path or "built",
            "phases": {ph: p.describe()
                       for ph, p in sorted(plan.phases.items())},
        },
        "modes": {},
    }
    if profile_path:
        # calibration truth: the measured profile's roofline predictions
        # vs a timed equivalent of each decode-graph layer's per-device
        # work on this host — median relative error is the gated headline
        # (a calibrated cost model that drifts is a regression)
        from repro.core import CostModel
        from repro.models.arch import ShapeSpec
        from repro.models.graph_export import export_graph
        from repro.profiling import layer_report, load_profile

        prof = load_profile(profile_path)
        graph = export_graph(arch, ShapeSpec(
            "bench_decode", max(prompt_buckets), max_batch, "decode"))
        cm_cal = CostModel.from_profile(prof, mesh_spec, training=False,
                                        phase="decode")
        calib = layer_report(graph, cm_cal)
        report["device_profile"] = {
            "path": profile_path,
            "device_kind": prof.device_kind,
            "fingerprint": prof.fingerprint(),
            "measured_flops": prof.measured_flops,
            "measured_hbm_bw": prof.measured_hbm_bw,
        }
        report["cost_model_rel_error"] = calib["median_rel_error"]
        report["cost_model_max_rel_error"] = calib["max_rel_error"]
        print(f"cost model calibration: median rel error "
              f"{calib['median_rel_error']:.3f} over "
              f"{calib['num_layers']} layers (max "
              f"{calib['max_rel_error']:.3f})")
    # (mode name, admission policy, block size, pool blocks, chunk): the
    # paged continuous/static pair measures scheduling, the dense
    # continuous baseline measures the paging memory/throughput delta,
    # and unchunked (same engine, prefill_chunk_tokens=0 — stall-the-
    # world prefill) is the chunking A/B oracle for the ITL win
    runs = [("continuous", "continuous", kv_block_size, kv_pool_blocks,
             chunk, None),
            ("unchunked", "continuous", kv_block_size, kv_pool_blocks, 0,
             None),
            ("static", "static", kv_block_size, kv_pool_blocks, 0, None)]
    if kv_block_size:
        runs.append(("dense", "continuous", 0, 0, chunk, None))
    if kv_quant and kv_block_size:
        # same trace, same chunked continuous engine, int8 paged pool —
        # the quantization A/B against the fp "continuous" mode above
        runs.append(("continuous_int8", "continuous", kv_block_size,
                     kv_pool_blocks, chunk, kv_quant))
    with use_mesh(mesh if n_dev > 1 else None):
        for mode, policy, bs, pool, ck, kvq in runs:
            engine = ServeEngine(params, arch, ServeConfig(
                max_batch=max_batch, max_len=max_len, policy=policy,
                kv_block_size=bs, kv_pool_blocks=pool or None,
                prefill_chunk_tokens=ck, q_chunk=256, kv_quant=kvq),
                plan=plan_q if kvq else plan)
            engine.warmup(buckets)
            report["modes"][mode] = run_mode(engine, trace)
            m = report["modes"][mode]
            print(f"{mode:>10}: {m['out_tok_per_s']:8.1f} out tok/s  "
                  f"wall {m['wall_s']*1e3:8.1f} ms  "
                  f"{m['decode_steps']} decode steps  "
                  f"p95 latency {m['latency_p95_s']*1e3:.0f} ms  "
                  f"itl p99 {m.get('itl_p99_ms', 0):.1f} ms  "
                  f"kv {m['kv_bytes_reserved']/2**20:.2f} MiB  "
                  f"prefix hit {m['prefix_hit_rate']:.2f}")
    modes = report["modes"]
    report["continuous_speedup"] = round(
        modes["continuous"]["out_tok_per_s"]
        / max(modes["static"]["out_tok_per_s"], 1e-9), 3)
    print(f"continuous/static throughput: {report['continuous_speedup']}x")
    if ("itl_p99_ms" in modes["continuous"]
            and "itl_p99_ms" in modes["unchunked"]):
        # < 1.0 means chunked prefill flattened the decode latency tail
        report["chunked_itl_p99_ratio"] = round(
            modes["continuous"]["itl_p99_ms"]
            / max(modes["unchunked"]["itl_p99_ms"], 1e-9), 3)
        print(f"chunked/unchunked itl p99: "
              f"{report['chunked_itl_p99_ratio']}x")
    # prefix sharing only materializes in the chunked paged mode (the
    # chunk is what skips the cached tokens) — surface its metrics top
    # level so the CI gate watches them like the other headline numbers
    report["prefix_hit_rate"] = modes["continuous"]["prefix_hit_rate"]
    report["prefill_tokens_saved"] = (
        modes["continuous"]["prefill_tokens_saved"])
    if report["prefix_hit_rate"] or report["prefill_tokens_saved"]:
        print(f"prefix cache: hit rate "
              f"{report['prefix_hit_rate']:.2f}, "
              f"{report['prefill_tokens_saved']} prefill tokens saved")
    if "dense" in modes:
        report["paged_speedup"] = round(
            modes["continuous"]["out_tok_per_s"]
            / max(modes["dense"]["out_tok_per_s"], 1e-9), 3)
        report["kv_reserved_frac"] = round(
            modes["continuous"]["kv_bytes_reserved"]
            / max(modes["dense"]["kv_bytes_reserved"], 1), 3)
        print(f"paged/dense throughput: {report['paged_speedup']}x  "
              f"kv reserved: {report['kv_reserved_frac']:.1%} of dense")
    if "continuous_int8" in modes:
        # headline quantization wins the CI gate watches: the int8/fp
        # reservation ratio (deterministic bytes — int8 payload + f32
        # scales over the bf16/f32 pool) and the teacher-forced logit
        # delta (pure numerics, no scheduling in the loop)
        report["quant_kv_reserved_frac"] = round(
            modes["continuous_int8"]["kv_bytes_reserved"]
            / max(modes["continuous"]["kv_bytes_reserved"], 1), 4)
        report["quant_speedup"] = round(
            modes["continuous_int8"]["out_tok_per_s"]
            / max(modes["continuous"]["out_tok_per_s"], 1e-9), 3)
        report["quant_logit_agreement"] = round(
            quant_logit_probe(mod, params, arch, arch.vocab), 6)
        print(f"int8 kv reserved: {report['quant_kv_reserved_frac']:.1%} "
              f"of fp  int8/fp throughput: {report['quant_speedup']}x  "
              f"max logit delta: {report['quant_logit_agreement']:.4g}")
    if train_stages not in (0, 1):
        # stage-dimension trajectory point: search the *train* phase with
        # the two-level pipeline search on a fixed synthetic 8-device mesh
        # (4 data x 2 model) — pure cost model, so stage_count and
        # pipeline_bubble_frac are deterministic and independent of the
        # runner's real device count; the serving trace above is untouched
        from repro.core.device import AxisSpec, ICI_BW, MeshSpec
        from repro.plans.search import search_phase_plan

        syn = MeshSpec(axes=(AxisSpec("data", 4, ICI_BW),
                             AxisSpec("model", 2, ICI_BW)))
        _, st, prov = search_phase_plan(
            arch, syn, "train", seq_len=max(prompt_buckets), batch=8,
            num_stages=train_stages, microbatches=train_microbatches)
        report["stage_count"] = st.num_stages if st is not None else 1
        report["pipeline_bubble_frac"] = prov.get("pipeline_bubble_frac", 0.0)
        report["train_pipeline"] = {
            "mesh": "synthetic-4x2",
            "seq_len": int(max(prompt_buckets)),
            "batch": 8,
            "microbatches": int(train_microbatches),
            "boundaries": list(st.boundaries) if st is not None else None,
            "interstage_bytes": prov.get("interstage_bytes"),
            "stage_costs_s": prov.get("stage_costs_s"),
            "cost_s": prov.get("cost_s"),
        }
        print(f"train pipeline: S={report['stage_count']} "
              f"M={train_microbatches} "
              f"bubble={report['pipeline_bubble_frac']:.3f}")
    Path(out).write_text(json.dumps(report, indent=1))
    print(f"wrote {out}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate (requests/s); <=0 = all "
                         "arrive at t=0")
    ap.add_argument("--prompt-buckets", type=int, nargs="+",
                    default=[16, 32, 64])
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=48)
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot cache row budget (0 = max prompt "
                         "bucket + gen-max); the dense baseline reserves "
                         "this per slot, paging only what is used")
    ap.add_argument("--kv-block-size", type=int, default=128,
                    help="tokens per paged-KV block (0 = dense rows "
                         "everywhere, skipping the paged-vs-dense "
                         "comparison)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="usable paged-pool blocks (0 = auto: every slot "
                         "holding the trace's worst-case request)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="length of the common prompt prefix a "
                         "--shared-frac fraction of requests open with "
                         "(0 = no shared segment); exercises the "
                         "copy-on-write prefix cache")
    ap.add_argument("--shared-frac", type=float, default=0.0,
                    help="fraction of requests that carry the shared "
                         "prefix")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8"],
                    help="additionally run a continuous_int8 mode (same "
                         "trace, int8 paged pool with per-row scales) and "
                         "report quant_kv_reserved_frac (int8/fp bytes), "
                         "quant_speedup and quant_logit_agreement (teacher-"
                         "forced max logit delta) for the CI gate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", default="uniform",
                    choices=["uniform", "data", "model", "owt", "searched"],
                    help="plan both modes execute under; 'searched' "
                         "searches prefill + decode phases per the device "
                         "mesh (the plan lands in the report JSON)")
    ap.add_argument("--plan", default="",
                    help="load a ParallelPlan JSON instead of building one")
    ap.add_argument("--train-stages", type=int, default=0,
                    help="also search a pipeline-staged *train* plan with "
                         "this many stages on a synthetic 8-device mesh "
                         "(pure cost model; -1 = auto) and record "
                         "stage_count / pipeline_bubble_frac in the "
                         "report for the CI gate; 0 = skip")
    ap.add_argument("--train-microbatches", type=int, default=8,
                    help="1F1B microbatch count M priced by the staged "
                         "train search")
    ap.add_argument("--save-plan", default="",
                    help="persist the plan JSON next to the report")
    ap.add_argument("--device-profile", default="",
                    help="measured DeviceProfile JSON (launch.profile); "
                         "calibrates the plan search's cost model and "
                         "records cost_model_rel_error + profile "
                         "provenance in the report")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (tiny model, few requests)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    kw = dict(arch_name=args.arch, width=args.width, depth=args.depth,
              vocab=args.vocab, max_batch=args.slots,
              n_requests=args.requests, rate=args.rate,
              prompt_buckets=tuple(args.prompt_buckets),
              gen_range=(args.gen_min, args.gen_max), out=args.out,
              seed=args.seed, strategy=args.strategy, plan_path=args.plan,
              save_plan=args.save_plan, kv_block_size=args.kv_block_size,
              kv_pool_blocks=args.kv_pool_blocks, max_len=args.max_len,
              shared_prefix_len=args.shared_prefix_len,
              shared_frac=args.shared_frac,
              train_stages=args.train_stages,
              train_microbatches=args.train_microbatches,
              kv_quant=args.kv_quant,
              profile_path=args.device_profile)
    if args.smoke:
        # CI-sized model, but the trace shape of the paged-KV acceptance
        # run: ragged 16-512 token prompts against a 2048-token row
        # budget, so kv_reserved_frac measures the real paging win; 75%
        # of requests open with a common 384-token (3-block) system
        # prompt so the prefix-cache gate exercises real hits
        kw.update(width=128, depth=2, vocab=256, max_batch=4,
                  n_requests=24, rate=200.0,
                  prompt_buckets=(16, 64, 256, 512),
                  gen_range=(2, 40), seed=1, max_len=2048,
                  shared_prefix_len=384, shared_frac=0.75)
    run_benchmark(**kw)


if __name__ == "__main__":
    main()
