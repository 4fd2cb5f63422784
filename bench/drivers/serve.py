"""Serving: the program's ``ServeEngine`` under open-loop traffic.

Set-up makes the weights from the seed, resolves the serving plan
(``resolve_serve_plan``), builds the engine (``ServeEngine(ServeConfig)``),
compiles every step width the mix's prompts can ask for (``warmup``),
and runs a ramp of the mix's own traffic so the window opens on a filled
server.  In the window the harness submits each request when it is due
(``submit``) and drives ``step``; a request's tokens are stamped when
the step that made them returns.  After the window the engine is freed
and the configuration family's reference scores a sample of the
finished requests.
"""

from __future__ import annotations

import gc
import importlib
import time
from collections import deque

import numpy as np

from bench.core import flops, weights
from bench.core.runtime import (Profiler, SetupParts, memory_peak_bytes,
                                 span)
from bench.core.spec import program_arch
from bench.reference import family

SPANS = ("engine.step", "engine.submit", "arrival pacing")
FAULTS = ("token", "state")
ALTER_EVERY = 7          # the "token" fault alters every 7th step's tokens


def _plant(engine, fault: str | None, vocab: int) -> None:
    """Break the timed path underneath the harness (tests only)."""
    import jax
    import jax.numpy as jnp

    if fault == "token":
        sample, calls = engine._sample, [0]

        def altered(logits):
            calls[0] += 1
            nxt = sample(logits)
            return (nxt + 1) % vocab if calls[0] % ALTER_EVERY == 0 else nxt
        engine._sample = altered
    elif fault == "state":
        from repro.serve import make_serve_fns

        _, step = make_serve_fns(engine.arch, engine.plan,
                                 q_chunk=engine.config.q_chunk)
        engine._step = jax.jit(
            lambda p, t, c, pos, q_lens=None, block_tables=None:
            (step(p, t, c, pos, q_lens, block_tables)[0],
             jax.tree.map(jnp.copy, c)))
    elif fault is not None:
        raise ValueError(f"unknown serving fault {fault!r}; have {FAULTS}")


def run(cell, opts, t_start: float) -> dict:
    from repro.kernels import dispatch
    from repro.launch.serve import resolve_serve_plan
    from repro.serve import PoolExhausted, Request, ServeConfig, ServeEngine

    setup = SetupParts(t_start)
    setup("start")
    cfg, tr = cell.config, cell.traffic
    arch = program_arch(cfg)
    gen = importlib.import_module(f"bench.traffic.{tr['generator']}")
    reqs = gen.schedule(tr, opts.seed, opts.seconds, cfg["vocab_size"],
                        rate=opts.rate)
    max_len = gen.max_len(tr)

    params = weights.make_params(cfg, opts.seed)
    weights.check_layout(params, arch)
    setup("weights")
    t = time.perf_counter()
    plan = resolve_serve_plan(
        arch, None, strategy=tr.get("strategy", "uniform"),
        prompt_len=int(np.median([len(r.prompt) for r in reqs])),
        max_batch=tr["slots"], max_len=max_len,
        kv_block_size=tr["kv_block_size"],
        prefill_chunk_tokens=tr["prefill_chunk_tokens"],
        shared_prefix_tokens=(tr.get("shared_prefix") or {}).get("tokens", 0))
    plan_s = time.perf_counter() - t
    engine = ServeEngine(params, arch, ServeConfig(
        max_batch=tr["slots"], max_len=max_len,
        kv_block_size=tr["kv_block_size"],
        kv_pool_blocks=tr.get("kv_pool_blocks"),
        prefill_chunk_tokens=tr["prefill_chunk_tokens"],
        q_chunk=tr.get("q_chunk", 256)), plan=plan)
    del params
    _plant(engine, opts.fault, cfg["vocab_size"])
    dispatch.reset_selection_counts()
    setup("engine")
    warmup_s = engine.warmup(gen.prompt_lengths(reqs))
    setup("warmup")

    # ---- ramp, then the window ------------------------------------- #
    t0 = time.perf_counter() + tr["ramp_s"]
    t_end = t0 + opts.seconds
    trace_s = min(tr["trace_seconds"], opts.seconds)
    trace_on = t0 + (opts.seconds - trace_s) / 2
    prof = Profiler() if opts.trace else None
    pending = deque(sorted(reqs, key=lambda r: r.due))
    tok_times: dict[int, list[float]] = {}
    finished: dict[int, list[int]] = {}
    failed: set[int] = set()
    late: list[float] = []
    steps: list[dict] = []
    trace_dir = None
    try:
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            opts.compiles.in_window = now >= t0
            if prof and not prof.on and trace_dir is None and now >= trace_on:
                prof.start()
            elif prof and prof.on and now >= trace_on + trace_s:
                trace_dir = prof.stop()
            with span("engine.submit"):
                while pending and t0 + pending[0].due <= now:
                    r = pending.popleft()
                    late.append(now - (t0 + r.due))
                    try:
                        engine.submit(Request(uid=r.uid, prompt=r.prompt,
                                              max_new_tokens=r.max_new_tokens))
                    except (PoolExhausted, ValueError):
                        failed.add(r.uid)
            if not engine.busy:
                nxt = t0 + pending[0].due if pending else t_end
                with span("arrival pacing"):
                    time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
                continue
            active = engine.scheduler.active
            pre = {st.request.uid: (st.pos, st.prefill_remaining)
                   for st in active.values()}
            prefill0 = engine.stats["prefill_tokens"]
            ts = time.perf_counter()
            with span("engine.step"):
                done = engine.step()
            te = time.perf_counter()
            counts = {st.request.uid: (len(st.generated), st.pos)
                      for st in engine.scheduler.active.values()}
            for c in done:
                counts[c.uid] = (len(c.tokens), None)
                finished[c.uid] = c.tokens
            for uid, (n, _) in counts.items():
                have = tok_times.setdefault(uid, [])
                have.extend([te] * (n - len(have)))
            steps.append(_step_work(cfg, pre, counts, ts, te,
                                    engine.stats["prefill_tokens"] - prefill0))
        if prof and prof.on:
            trace_dir = prof.stop()
    except BaseException:
        if prof:
            prof.cleanup()
        raise
    opts.compiles.in_window = False

    # ---- end-to-end metrics ----------------------------------------- #
    due = [r for r in reqs if 0 <= r.due < opts.seconds]
    ttft = []
    for r in due:
        times = tok_times.get(r.uid) or [np.inf]
        ttft.append(min(times[0], t_end) - (t0 + r.due))
    toks = [t for ts_ in tok_times.values() for t in ts_ if t0 <= t < t_end]
    itl = [b - a for ts_ in tok_times.values() for a, b in zip(ts_, ts_[1:])
           if t0 <= b < t_end]
    metrics = {
        "setup_s": t0 - t_start,
        "output_tokens_per_s": len(toks) / opts.seconds,
        "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
        "itl_p95_ms": float(np.percentile(itl, 95)) * 1e3,
    }
    mem = memory_peak_bytes(cell.chips)
    in_window = [s for s in steps if s["ts"] >= t0]
    counters = {
        "plan_search_s": plan_s, "warmup_s": warmup_s,
        "setup_parts_s": dict(setup.parts, ramp=t0 - setup.last),
        "prefix_hit_rate": engine.prefix_hit_rate,
        "kv_bytes_reserved": engine.kv_bytes_reserved,
        "peak_blocks_in_use": engine.peak_blocks_in_use,
        "steps_in_window": len(in_window),
        "requests_due": len(due), "requests_finished": len(finished),
        "itl_samples": len(itl),
        "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
        "generator_late_max_ms": max(late, default=0.0) * 1e3,
        "generator_late_p50_ms": float(np.median(late)) * 1e3 if late else 0.0,
        "backlog_at_close": len(engine.queue),
        "in_flight_at_close": len(engine.scheduler.active),
        "selection_counts": {f"{o}/{b}": n for (o, b), n in
                             dispatch.selection_counts().items()},
        "spmd_skips": {f"{o}/{b}": n for (o, b), n in
                       dispatch.spmd_skips().items()},
    }
    record = {"cell": cell, "steps": in_window,
              "trace_dir": trace_dir, "trace_window": (
                  (prof.started, prof.stopped) if prof else None),
              "counters": counters, "profiler": prof}

    # ---- correctness: the reference over a sample of finished requests - #
    engine = None
    gc.collect()
    readings, control = _check(cfg, tr, reqs, finished, opts, max_len)
    return {"attempted": len(due), "failed": len(failed & {r.uid for r in due}),
            "metrics": metrics, "readings": readings, "control": control,
            "record": record, "memory_peak_bytes": mem, "spans": SPANS}


def _step_work(cfg, pre, counts, ts, te, prefill_tokens) -> dict:
    """Model FLOPs of one engine step, its width and the KV lengths its
    single-token decode pass attended over, from the slots' positions
    around it.  A slot's prompt tokens in the step are how far its
    position moved; a slot admitted inside the step takes what the
    engine's prefill count says is left.  The width is the most prompt
    tokens one slot took (1 where none did): the step's compiled program,
    and only a width-1 step runs the Pallas paged-decode kernel."""
    dec = [pos + 1 for pos, rem in pre.values() if rem == 0]
    work = sum(flops.serve_flops(cfg, p - 1, 1) for p in dec)
    grants, left = [], prefill_tokens
    for uid, (pos, rem) in pre.items():
        after = counts.get(uid, (0, None))[1]
        if rem > 0 and after is not None and after > pos:
            grants.append((pos, after - pos))
            left -= after - pos
    for uid, (_, after) in counts.items():
        if uid not in pre and after is not None and left > 0:
            g = min(after, left)
            grants.append((after - g, g))
            left -= g
    work += sum(flops.serve_flops(cfg, start, g) for start, g in grants)
    return {"ts": ts, "te": te, "flops": work, "decode_kv_lens": dec,
            "width": max([1] + [g for _, g in grants]),
            "tokens": len(dec) + prefill_tokens}


def _check(cfg, tr, reqs, finished, opts, max_len):
    """How far below the reference's best each served token's logit lies,
    over a seeded sample of finished requests that holds the longest of
    them: the widest gap and the mean gap over the sample's tokens.  With
    ``opts.control``, the same for the control (the reference with int8
    weights, bfloat16 activations: the program's products already run in
    bfloat16) and for one altered token per request."""
    import jax.numpy as jnp

    ref = family(cfg)
    by_uid = {r.uid: r for r in reqs}
    done = sorted(finished)
    if not done:
        return {"finished_requests": 0.0}, None
    rng = np.random.default_rng(opts.seed)
    longest = max(done, key=lambda u: len(by_uid[u].prompt) + len(finished[u]))
    rest = [u for u in done if u != longest]
    k = min(len(rest), tr["check"]["requests"] - 1)
    sample = [longest] + sorted(rng.choice(rest, size=k, replace=False).tolist()
                                if k else [])
    params = weights.make_params(cfg, opts.seed)
    gap_fn = ref.served_gap_fn(cfg)
    if opts.control:
        low = ref.int8_weights(params)
        pick_fn = ref.control_pick_fn(cfg)
    gaps_all = []
    for uid in sample:
        prompt, served = list(by_uid[uid].prompt), finished[uid]
        seq = prompt + served[:-1]
        rows = np.arange(len(prompt) - 1, len(seq))
        tokens = np.zeros(max_len, np.int32)
        tokens[:len(seq)] = seq
        targets = np.zeros((max_len, 3), np.int32)
        targets[rows, 0] = served
        if opts.control:
            pick = np.asarray(pick_fn(low, jnp.asarray(tokens)))
            targets[rows, 1] = pick[rows]
            j = rng.integers(len(served))
            targets[rows, 2] = served
            targets[rows[j], 2] = (served[j] + 1) % cfg["vocab_size"]
        best, at = gap_fn(params, jnp.asarray(tokens), jnp.asarray(targets))
        gaps_all.append(np.asarray(best)[rows, None] - np.asarray(at)[rows])
    gaps = np.concatenate(gaps_all)          # (served tokens, 3)
    widest, mean = gaps.max(axis=0), gaps.mean(axis=0)
    readings = {"widest_logit_gap": float(widest[0]),
                "mean_logit_gap": float(mean[0])}
    print(f"[check] reference over {len(sample)} finished requests, "
          f"{len(gaps)} served tokens (longest: uid {longest}, "
          f"{len(by_uid[longest].prompt)} + {len(finished[longest])}); "
          f"served tokens not the reference's first: "
          f"{float((gaps[:, 0] > 0).mean()):.4f}", flush=True)
    control = None
    if opts.control:
        control = {"control_widest_logit_gap": float(widest[1]),
                   "control_mean_logit_gap": float(mean[1]),
                   "control_not_first_share": float((gaps[:, 1] > 0).mean()),
                   "altered_widest_logit_gap": float(widest[2]),
                   "altered_mean_logit_gap": float(mean[2])}
    return readings, control
