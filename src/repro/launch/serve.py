"""Serving driver: request queue in, completions out.

Decoder-only LMs run through the continuous-batching engine
(``repro.serve.ServeEngine``): a fixed pool of ``--batch`` cache slots,
requests admitted into free slots mid-decode, ragged single-token decode
with per-slot positions, slots retired on EOS / max-tokens.  KV is paged
(``--kv-block-size`` tokens per block, block-table indirection, lazy
allocation; ``--kv-pool-blocks`` bounds the pool) — ``--kv-block-size
0`` keeps the dense per-slot ``max_len`` rows, and ``--kv-quant int8``
stores the paged blocks as int8 with per-row scales (quantize on write,
dequantize on read, ~4x less pool memory).  Prompts prefill in
chunks *inside* the decode batch (mixed steps; ``--prefill-chunk-tokens``
sets the per-step budget, 0 restores stall-the-world prefill) so
in-flight decodes never stall behind an admission.  Identical whole
prompt blocks are shared between requests through the refcounted
copy-on-write prefix index (``--no-prefix-cache`` disables it,
``--prefix-evict`` picks the retention policy); a hit skips prefill for
the cached tokens and charges admission only the new blocks.
``--no-continuous`` keeps the lockstep static-batch oracle (admit a
full batch, drain it, admit the next) for A/B comparison.

The strategy flags mirror ``repro.launch.train``: ``--strategy
{uniform,data,model,owt,searched}`` builds a phase-aware ParallelPlan
(prefill priced as a batch-1 prompt, decode as a single-token ragged
batch over the slot pool — the searched configs differ per phase),
``--plan`` loads one from JSON instead, ``--save-plan`` persists the
plan next to the run.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --width 256 --depth 4 --batch 4 --requests 8 \
        --prompt-len 64 --gen 32 --strategy searched --save-plan plan.json

Both jitted fns are warmed up on a dummy step before anything is timed
and compile seconds are reported separately — reported tok/s is steady
state, not steady state diluted by jit compilation.  The encoder-decoder
arch (seamless) keeps a static lockstep loop (its cache carries a
non-slot-shaped memory leaf), with the same warm-up discipline.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.sharding import use_mesh
from repro.data import make_dataset
from repro.kernels import dispatch as kernel_dispatch
from repro.models import model_module
from repro.models.arch import ShapeSpec
from repro.plans import ParallelPlan, STRATEGIES, resolve_plan
from repro.serve import (PrefixCache, Request, ServeConfig, ServeEngine,
                         make_serve_fns)

from .compile_cache import enable_compile_cache
from .mesh import host_mesh
from .train import reduced_arch


def resolve_serve_plan(arch, mesh_spec, *, plan_path: str = "",
                       strategy: str = "uniform", prompt_len: int,
                       max_batch: int, max_len: int,
                       kv_block_size: int = 0,
                       typical_tokens: int | None = None,
                       prefill_chunk_tokens: int = 0,
                       shared_prefix_tokens: int = 0,
                       kv_quant: str | None = None,
                       save_plan: str = "",
                       profile_path: str = "") -> ParallelPlan:
    """Serving preset of :func:`repro.plans.resolve_plan`: the phases a
    serving process executes are prefill + decode (shared by this
    driver and the serving benchmark).

    With a paged cache (``kv_block_size > 0``) the decode phase is
    priced at the per-slot *allocated-blocks* depth — ``typical_tokens``
    (a request's realistic prompt+output budget, default
    ``prompt_len``-based ``max_len``) rounded up to whole blocks —
    instead of the dense ``max_len`` reservation, so the searched decode
    plan sees the cache traffic the engine actually moves.

    With chunked prefill (``prefill_chunk_tokens > 0``) the decode phase
    is priced as the engine's *mixed* step: each step carries
    ``max_batch - 1`` single-token decode slots plus one
    ``prefill_chunk_tokens``-wide prefill chunk, so the amortized
    per-slot query width is ``ceil((max_batch - 1 + chunk) / max_batch)``
    and the searched decode plan sees the matmul work the mixed step
    actually does.

    With prefix caching, ``shared_prefix_tokens`` of that typical budget
    live in blocks shared across the whole slot pool — physically
    allocated *once*, not per request — so the amortized per-slot depth
    is ``unique + ceil(shared / max_batch)``.  The pricing stays at
    allocated-physical-block depth: the searched decode plan sees the
    KV bytes the pool actually holds, which is the whole point of
    sharing (PaSE's argument that the search is only as good as the
    cost model's memory truth).

    With ``kv_quant="int8"`` the decode cache read is priced at the
    quantized pool's stored width (1 byte/elem + the amortized f32
    per-row scale) instead of the fp width, and the plan's meta records
    the quantization it was searched for.
    """
    kv_tokens = None
    if kv_block_size:
        tokens = min(typical_tokens or max_len, max_len)
        shared = min(max(0, shared_prefix_tokens), tokens)
        if shared and max_batch > 1:
            tokens = (tokens - shared) + -(-shared // max_batch)
        kv_tokens = -(-tokens // kv_block_size) * kv_block_size
    q_tokens = None
    if prefill_chunk_tokens > 0:
        q_tokens = -(-(max_batch - 1 + prefill_chunk_tokens) // max_batch)
    plan = resolve_plan(
        arch, mesh_spec, phases=("prefill", "decode"),
        plan_path=plan_path, strategy=strategy, save_plan=save_plan,
        prompt_len=prompt_len, max_batch=max_batch, max_len=max_len,
        decode_kv_tokens=kv_tokens, decode_q_tokens=q_tokens,
        decode_kv_quant=kv_quant if kv_block_size else None,
        profile_path=profile_path)
    # A staged *train* phase riding a loaded plan file is fine (serving
    # ignores it); a pipeline-staged decode is not executable here —
    # token-level decode pipelining is a named follow-up — so refuse it
    # loudly rather than silently running stage 0's configs everywhere.
    dec = plan.stage_for("decode")
    if dec.num_stages > 1:
        raise ValueError(
            f"plan's decode phase is pipeline-staged (S={dec.num_stages}); "
            f"the serve engine executes a single mesh — token-level decode "
            f"pipelining is not implemented yet.  Re-search the serve plan "
            f"without stages or load a plan whose decode phase is "
            f"single-stage.")
    pre = plan.stage_for("prefill")
    if pre.num_stages > 1:
        print(f"serve: note — plan's prefill phase is pipeline-staged "
              f"(S={pre.num_stages}); serving runs the whole model on one "
              f"mesh under stage-0 semantics (per-layer configs only, no "
              f"pipelining)")
    return plan


def _serve_encdec(args, arch, plan) -> None:
    """Legacy lockstep path for the encoder-decoder arch."""
    mod = model_module(arch)
    max_len = args.prompt_len + args.gen
    params = mod.init_encdec(jax.random.PRNGKey(0), arch, jnp.float32)
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    ds = make_dataset(arch, shape)
    batch = jax.tree.map(jnp.asarray, ds.batch_at(0))
    enc_len = batch["frames"].shape[1]

    prefill_jit, decode_jit = make_serve_fns(
        arch, plan, q_chunk=256, kernel_backend=args.kernel_backend or None,
        jit=True)

    def fresh_cache():
        return mod.init_cache(arch, args.batch, max_len, jnp.float32,
                              enc_len=enc_len)

    # warm up (compile) both fns on throwaway caches before timing
    t0 = time.time()
    logits, warm = prefill_jit(params, batch, fresh_cache())
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    logits, warm = decode_jit(params, tok, warm, jnp.int32(args.prompt_len))
    jax.block_until_ready(logits)
    t_compile = time.time() - t0

    t0 = time.time()
    logits, cache = prefill_jit(params, batch, fresh_cache())
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    # the encdec dataset halves --prompt-len between encoder frames and
    # decoder tokens; rate math must use the actual decoder prompt length
    pos = batch["tokens"].shape[1]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode_jit(params, tok, cache, jnp.int32(pos + i))
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"arch={arch.name} batch={args.batch} prompt={pos} "
          f"gen={args.gen} mode=static(encdec)")
    print(f"compile: {t_compile:.2f} s (excluded from the rates below)")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch*pos/max(t_prefill,1e-9):.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({args.batch*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("sample generations (token ids):")
    for row in gen[:2]:
        print("  ", row[:24].tolist())


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--batch", type=int, default=4,
                    help="cache slot pool size (max in-flight requests)")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests to serve (default 2x --batch)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--no-continuous", action="store_true",
                    help="static-batch oracle: admit a full batch, drain "
                         "it, admit the next (the pre-engine lockstep)")
    ap.add_argument("--kv-block-size", type=int, default=128,
                    help="tokens per paged-KV block (0 = dense per-slot "
                         "max_len rows, the pre-paging layout)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="usable blocks in the paged KV pool (0 = "
                         "dense-equivalent capacity); smaller pools "
                         "serve the same slots in less memory, gated by "
                         "block-budget admission")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=-1,
                    help="per-step prompt-token budget for chunked "
                         "prefill riding the mixed decode step (-1 = "
                         "engine default: 2*block_size paged, 256 dense; "
                         "0 = stall-the-world prefill, the pre-chunking "
                         "behavior)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable copy-on-write prefix sharing in the "
                         "paged pool (the sharing-off oracle; sharing is "
                         "on by default wherever it is sound: paged + "
                         "chunked + attention-only arch)")
    ap.add_argument("--prefix-evict", default="lru",
                    choices=list(PrefixCache.EVICTION),
                    help="prefix-index retention: lru keeps published "
                         "blocks warm after their requests retire "
                         "(evicted leaf-first when the pool runs dry), "
                         "none shares only between concurrent requests")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8"],
                    help="paged-pool KV quantization: int8 stores KV "
                         "blocks as int8 with per-row f32 scales riding "
                         "the block table (quantize on write, dequantize "
                         "after the block gather); requires "
                         "--kv-block-size > 0")
    ap.add_argument("--shared-prefix-tokens", type=int, default=0,
                    help="typical shared-prefix length for decode-phase "
                         "plan pricing: these tokens are allocated once "
                         "across the pool, so per-slot KV depth is "
                         "amortized (0 = no sharing assumed)")
    ap.add_argument("--strategy", default="uniform",
                    choices=list(STRATEGIES),
                    help="parallelization plan: uniform/data/model/owt "
                         "baselines or the searched per-phase plan "
                         "(prefill + decode searched separately)")
    ap.add_argument("--plan", default="",
                    help="load a ParallelPlan JSON (from --save-plan here "
                         "or on the train driver); overrides --strategy, "
                         "refuses an arch mismatch")
    ap.add_argument("--save-plan", default="",
                    help="write the plan (searched or baseline) to this "
                         "JSON path for later --plan runs")
    ap.add_argument("--width", type=int, default=0,
                    help="cut d_model to this width (0 = published)")
    ap.add_argument("--depth", type=int, default=0,
                    help="cut the layer count to this depth (0 = published)")
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--experts", type=int, default=0)
    ap.add_argument("--kernel-backend", default="",
                    help="force a kernel dispatch backend "
                         "(pallas|interpret|xla|ref) for every op — "
                         "attention, wkv6, mamba_scan, moe_dispatch_combine;"
                         " default auto")
    ap.add_argument("--device-profile", default="",
                    help="measured DeviceProfile JSON (launch.profile); "
                         "calibrates the plan search's cost model to this "
                         "host instead of the analytic constants")
    ap.add_argument("--autotune-cache-dir", default="",
                    help="directory for the persistent Pallas block-size "
                         "autotune cache (default ~/.cache/repro/autotune; "
                         "same as REPRO_AUTOTUNE_CACHE_DIR) — a restart on "
                         "the same device kind skips re-tuning")
    return ap


def run(argv=None) -> dict:
    """Build the plan and the engine, serve the synthetic requests, print
    the report and return it: ``completions``, ``compile_s`` (warm-up),
    ``wall_s`` and the engine ``stats``, plus the ``arch`` and
    ``params`` served (so a caller can check the logits against the
    model).  The encoder-decoder arch returns ``completions=None``."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.autotune_cache_dir:
        os.environ[kernel_dispatch.ENV_CACHE_DIR] = args.autotune_cache_dir

    arch = reduced_arch(configs.get(args.arch), args.width, args.depth,
                        args.vocab, args.experts)
    n_dev = jax.device_count()
    mesh, mesh_spec = host_mesh(n_dev)
    max_len = args.prompt_len + args.gen
    # the plan prices decode with the chunk budget the engine will run;
    # mirror ServeEngine's auto default (2*block_size paged, 256 dense)
    chunk = args.prefill_chunk_tokens
    if chunk < 0:
        chunk = 2 * args.kv_block_size if args.kv_block_size else 256
    chunk = min(chunk, max_len)
    plan = resolve_serve_plan(
        arch, mesh_spec if n_dev > 1 else None, plan_path=args.plan,
        strategy=args.strategy, prompt_len=args.prompt_len,
        max_batch=args.batch, max_len=max_len,
        kv_block_size=args.kv_block_size, prefill_chunk_tokens=chunk,
        shared_prefix_tokens=args.shared_prefix_tokens,
        kv_quant=None if args.kv_quant == "none" else args.kv_quant,
        save_plan=args.save_plan, profile_path=args.device_profile)
    if arch.enc_layers:
        with use_mesh(mesh if n_dev > 1 else None):
            _serve_encdec(args, arch, plan)
        return {"arch": arch, "completions": None}

    mod = model_module(arch)
    n_requests = args.requests or 2 * args.batch
    params = mod.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    ds = make_dataset(arch, shape)
    prompts = []
    for i in range(-(-n_requests // args.batch)):
        prompts.extend(np.asarray(ds.batch_at(i)["tokens"]))
    requests = [Request(uid=i, prompt=prompts[i][:args.prompt_len],
                        max_new_tokens=args.gen)
                for i in range(n_requests)]

    mode = "static" if args.no_continuous else "continuous"
    with use_mesh(mesh if n_dev > 1 else None):
        engine = ServeEngine(
            params, arch,
            ServeConfig(
                max_batch=args.batch, max_len=max_len, policy=mode,
                kv_block_size=args.kv_block_size,
                kv_pool_blocks=args.kv_pool_blocks or None,
                prefill_chunk_tokens=chunk, q_chunk=256,
                kernel_backend=args.kernel_backend or None,
                prefix_cache=not args.no_prefix_cache,
                prefix_evict=args.prefix_evict,
                kv_quant=None if args.kv_quant == "none" else args.kv_quant),
            plan=plan)
        # warm up on the *actual* request prompt lengths — for frontend
        # (VLM) archs the dataset emits prompts shorter than
        # --prompt-len, and a mis-bucketed warmup would push the real
        # prefill compile back into the timed path
        t_compile = engine.warmup(sorted({len(r.prompt) for r in requests}))

        t0 = time.time()
        completions = engine.run(requests)
        wall = time.time() - t0

    s = engine.stats
    out_tokens = sum(len(c.tokens) for c in completions)
    kv_desc = (f"paged(bs={engine.block_size}, "
               f"peak_blocks={engine.peak_blocks_in_use}"
               + (f", quant={engine.kv_quant}" if engine.kv_quant else "")
               + ")"
               if engine.paged else "dense")
    print(f"arch={arch.name} slots={args.batch} requests={n_requests} "
          f"prompt={args.prompt_len} gen<={args.gen} mode={mode} "
          f"plan={plan.strategy_name} devices={n_dev} kv={kv_desc}")
    print(f"kv reserved: {engine.kv_bytes_reserved/2**20:.2f} MiB")
    print(f"compile: {t_compile:.2f} s (excluded from the rates below)")
    if engine.chunked:
        # prompt tokens ride the mixed steps: no separate prefill phase,
        # so all wall time (and the prompt work) is under decode_s
        print(f"prefill: chunked — {int(s['prefill_tokens'])} prompt "
              f"tokens rode the mixed steps (chunk={engine.chunk})")
        print(f"mixed:   {s['decode_s']*1e3:.1f} ms over "
              f"{int(s['decode_steps'])} steps "
              f"({(s['decode_tokens']+s['prefill_tokens'])/max(s['decode_s'],1e-9):.0f} tok/s incl. prompt)")
    else:
        print(f"prefill: {s['prefill_s']*1e3:.1f} ms "
              f"({s['prefill_tokens']/max(s['prefill_s'],1e-9):.0f} tok/s)")
        print(f"decode:  {s['decode_s']*1e3:.1f} ms over "
              f"{int(s['decode_steps'])} ragged steps "
              f"({s['decode_tokens']/max(s['decode_s'],1e-9):.0f} tok/s)")
    if engine.prefix is not None:
        print(f"prefix cache: hit_rate={engine.prefix_hit_rate:.2f} "
              f"prefill_tokens_saved={engine.prefill_tokens_saved} "
              f"(evict={engine.config.prefix_evict}, "
              f"cached_blocks={engine.prefix.cached_blocks})")
    if engine.itl_samples:
        itl = np.percentile(np.asarray(engine.itl_samples) * 1e3,
                            [50, 95, 99])
        print(f"inter-token latency: p50={itl[0]:.1f} ms "
              f"p95={itl[1]:.1f} ms p99={itl[2]:.1f} ms")
    print(f"end-to-end: {out_tokens} output tokens in {wall*1e3:.1f} ms "
          f"({out_tokens/max(wall,1e-9):.0f} tok/s)")
    print("sample generations (token ids):")
    for c in sorted(completions, key=lambda c: c.uid)[:2]:
        print(f"  uid={c.uid} [{c.finish_reason}]", c.tokens[:24])
    return {"arch": arch, "params": params, "requests": requests,
            "completions": completions, "compile_s": t_compile,
            "wall_s": wall, "stats": dict(s)}


def main() -> None:
    run()


if __name__ == "__main__":
    main()
