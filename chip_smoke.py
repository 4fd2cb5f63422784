#!/usr/bin/env python3
"""Bring-up smoke: the train and serve entry points on one TPU v5e at the
published widths of olmo-1b, in this one process.

    python chip_smoke.py               # one chip: train, serve, dispatch
    python chip_smoke.py --four-chips  # the 2x2 host: sharded training only

One chip:

* train — ``repro.launch.train.run``: olmo-1b at published widths with
  the depth cut to 4 layers, seq 2048, global batch 4, 10 steps of the
  searched plan on one repeated batch.  Fails unless every loss is
  finite, the first-step nll lies within 0.5 of ln(vocab) (random weights
  predict uniformly) and the last nll is below the first.
* serve — ``repro.launch.serve.run``: olmo-1b at full depth, 8 slots,
  paged KV at block size 128, default chunked prefill, 16 requests of
  512 prompt and 64 new tokens.  Fails unless every request completes,
  and unless a teacher-forced stream through the paged cache
  (``decode_step``) agrees with ``lm.forward`` over the same tokens.
* dispatch — the per-op backend counts of each phase.  Training flash
  attention and single-token paged decode must resolve to ``pallas``;
  no op may resolve to ``interpret``.

Four chips: the same train cut on a 2x2 ``(data, model)`` mesh under the
searched plan and under the ``data`` baseline, same seed and batches.
Fails unless their losses agree within a stated tolerance and some matmul
weight of the searched run is physically split across devices.

Times printed here come from one bring-up run each; they are not
benchmark numbers.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``; any failed phase exits non-zero before
it.  Without a TPU, or without the repo's ``src/`` next to this file, the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "olmo-1b"
TRAIN_ARGS = ["--arch", ARCH, "--depth", "4", "--seq", "2048", "--batch", "4",
              "--strategy", "search", "--ckpt-every", "0", "--log-every", "1"]
SERVE_ARGS = ["--arch", ARCH, "--batch", "8", "--requests", "16",
              "--prompt-len", "512", "--gen", "64", "--kv-block-size", "128"]
# Teacher-forced check: both paths run f32 at the highest matmul
# precision, so they differ only in f32 summation order (the flash
# kernel's 256-token tiles against the paged kernel's 128-token pages,
# batch-1 decode against a 256-token forward), ~1e-6 relative per op.
# Compounded over 16 layers that stays far below 1e-3 on logits of order
# one, while a wrong position, mask or page lookup moves them by more
# than 1e-2.
LOGIT_TOL = 1e-3
# Searched vs data plan: the same f32 math partitioned differently over
# 2x2 chips, at default matmul precision (bf16 passes).  Partial sums in
# another order move the ~10.8 nll by ~1e-4 per step and a few Adam
# steps do not amplify that past 5e-3; a wrong sharding or a missing
# gradient reduction moves it by more than 1e-1.
LOSS_TOL = 5e-3
FOUR_CHIP_STEPS = 4    # per plan: enough Adam updates to show drift


class PhaseFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def print_backends(phase: str, dispatch) -> dict:
    counts = dispatch.selection_counts()
    print(f"[{phase}] resolved backends (op/backend: traces):")
    for (op, backend), n in sorted(counts.items()):
        print(f"    {op}/{backend}: {n}")
    check(not any(b == "interpret" for _, b in counts),
          f"{phase}: an op resolved to interpret: {counts}")
    return counts


def train_phase(train, dispatch) -> None:
    dispatch.reset_selection_counts()
    # One batch, repeated: the synthetic stream's marginal is uniform over
    # 50304 tokens and its bigram map is not learnable in 10 steps, so on
    # fresh batches the nll only wanders within batch noise; on a repeated
    # batch it must fall if the gradients and the update are right.
    res = train.run(TRAIN_ARGS + ["--steps", "10", "--overfit-batches", "1"])
    hist = res["history"]
    nll = [h["nll"] for h in hist]
    print(f"[train] plan={res['plan']} mesh={res['mesh']} steps={len(hist)}"
          f" nll first={nll[0]:.4f} last={nll[-1]:.4f}")
    print(f"[train] compile+first step {res['compile_s']:.2f} s, steady "
          f"{res['step_s']:.4f} s/step ({res['tokens_per_step']} tokens/step;"
          f" one bring-up run, not a benchmark)")
    check(all(math.isfinite(h["loss"]) for h in hist), "train: non-finite loss")
    vocab_nll = math.log(res["vocab"])
    check(abs(nll[0] - vocab_nll) < 0.5,
          f"train: first nll {nll[0]:.4f} not within 0.5 of "
          f"ln({res['vocab']}) = {vocab_nll:.4f}")
    check(nll[-1] < nll[0], f"train: nll did not fall ({nll[0]} -> {nll[-1]})")
    counts = print_backends("train", dispatch)
    check(counts.get(("flash_attention", "pallas"), 0) > 0,
          "train: flash_attention did not resolve to pallas")


def teacher_forced_delta(res, *, tokens: int = 256, block_size: int = 128,
                         seed: int = 0) -> tuple[float, float]:
    """Feed one random token stream through a paged cache one token at a
    time (``decode_step``, a non-identity block table) and through
    ``lm.forward`` in one pass; return the max absolute logit delta and
    the reference logits' standard deviation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model_module

    arch, params = res["arch"], res["params"]
    mod = model_module(arch)
    pages = -(-tokens // block_size)
    toks = np.random.default_rng(seed).integers(1, arch.vocab, tokens)
    toks = jnp.asarray(toks, jnp.int32)
    # physical blocks in reverse page order, block 0 left as trash
    bt = jnp.arange(pages, 0, -1, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: mod.forward(p, {"tokens": t}, arch)[0])(
            params, toks[None])
        step = jax.jit(lambda p, t, c, pos: mod.decode_step(
            p, t, c, pos, arch, block_tables=bt), donate_argnums=(2,))
        cache = mod.init_paged_cache(arch, pages + 1, block_size, 1,
                                     jnp.float32)
        delta = jnp.zeros((), jnp.float32)
        for i in range(tokens):
            lg, cache = step(params, toks[None, i:i + 1], cache,
                             jnp.full((1,), i, jnp.int32))
            delta = jnp.maximum(delta, jnp.max(jnp.abs(lg[0, 0] - ref[0, i])))
    return float(delta), float(jnp.std(ref))


def serve_phase(serve, dispatch) -> None:
    dispatch.reset_selection_counts()
    res = serve.run(SERVE_ARGS)
    done, want = res["completions"], res["requests"]
    check(sorted(c.uid for c in done) == sorted(r.uid for r in want),
          f"serve: {len(done)} of {len(want)} requests completed")
    check(all(len(c.tokens) == r.max_new_tokens
              for c, r in zip(sorted(done, key=lambda c: c.uid),
                              sorted(want, key=lambda r: r.uid))),
          "serve: a request stopped short of its new tokens")
    print(f"[serve] {len(done)} requests completed, "
          f"{sum(len(c.tokens) for c in done)} new tokens; warm-up/compile "
          f"{res['compile_s']:.2f} s, serving {res['wall_s']:.2f} s (one "
          f"bring-up run, not a benchmark)")
    counts = print_backends("serve", dispatch)
    check(counts.get(("paged_decode_attention", "pallas"), 0) > 0,
          "serve: single-token paged decode did not resolve to pallas")
    delta, scale = teacher_forced_delta(res)
    del res
    print(f"[serve] teacher-forced max |logit delta| paged decode vs "
          f"lm.forward = {delta:.3e} (logit std {scale:.3f}, tol "
          f"{LOGIT_TOL:g})")
    check(delta <= LOGIT_TOL, f"serve: logit delta {delta} > {LOGIT_TOL}")


def four_chip_phase(train, dispatch) -> None:
    args = TRAIN_ARGS + ["--steps", str(FOUR_CHIP_STEPS)]
    dispatch.reset_selection_counts()
    searched = train.run(args)
    skips = dispatch.spmd_skips()
    base = train.run([a if a != "search" else "data" for a in args])
    check(searched["mesh"] == {"data": 2, "model": 2},
          f"four chips: mesh is {searched['mesh']}, not 2x2 (data, model)")
    for name, res in (("searched", searched), ("data", base)):
        print(f"[four] {name}: plan={res['plan']} compile+first step "
              f"{res['compile_s']:.2f} s, steady {res['step_s']:.4f} s/step "
              f"(one bring-up run, not a benchmark)")
    gap = 0.0
    for a, b in zip(searched["history"], base["history"]):
        print(f"[four] step {a['step']}: nll searched={a['nll']:.5f} "
              f"data={b['nll']:.5f}")
        check(math.isfinite(a["loss"]) and math.isfinite(b["loss"]),
              "four chips: non-finite loss")
        gap = max(gap, abs(a["nll"] - b["nll"]))
    print(f"[four] max |nll searched - nll data| = {gap:.3e} "
          f"(tol {LOSS_TOL:g})")
    check(gap <= LOSS_TOL, f"four chips: losses differ by {gap}")
    split = [w for w in searched["weight_shards"]
             if w["devices"] > 1 and w["shard_shapes"] != [w["shape"]]]
    for w in split:
        print(f"[four] split weight {w['name']}: {w['shape']} held as "
              f"{w['shard_shapes']} over {w['devices']} devices")
    check(bool(split), "four chips: no matmul weight is split across devices")
    print("[four] ops that dropped pallas under the mesh (spmd_safe=False):"
          f" {sorted({op for op, _ in skips}) or 'none'}")
    print_backends("four", dispatch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on a 2x2 host")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the program is missing ({SRC}/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.kernels import dispatch
    from repro.launch import serve, train
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile "
          f"cache {enable_compile_cache()}")
    try:
        if args.four_chips:
            four_chip_phase(train, dispatch)
        else:
            train_phase(train, dispatch)
            serve_phase(serve, dispatch)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
