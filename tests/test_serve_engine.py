"""Continuous-batching serve engine: token-for-token agreement with the
per-request oracle under staggered admits/retirements and ragged lengths,
slot-reuse hygiene (a retired request's state cannot leak into its
successor), per-slot decode position handling, and scheduler semantics.

The oracle is the pre-engine serving path: batch-1 prefill + scalar-pos
decode.  Every device op on the decode path is row-independent (GQA
attention, the mamba/wkv6 recurrences, per-batch-row-grouped MoE
dispatch), so agreement is exact, not approximate.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import lm
from repro.serve import (Request, ServeConfig, ServeEngine, SlotScheduler,
                         write_slot)

# one arch per family on the serving path: dense GQA attention, MoE,
# RWKV6 recurrence, Mamba-hybrid (mamba + attn + MoE interleave)
ARCHS = ["llama3_2_1b", "olmoe_1b_7b", "rwkv6_1b6", "jamba_1_5_large"]


def _arch(name):
    arch = C.reduced(name)
    if arch.n_experts:
        # high capacity: routing drops would otherwise depend on batch
        # composition and generation could not be batch-size-invariant
        arch = dataclasses.replace(arch, capacity_factor=8.0)
    return arch


def _params(arch):
    return lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)


def _oracle(params, arch, prompt, max_new, max_len, eos_id=None):
    """Batch-1 prefill + scalar-position decode (the static serving path
    before the engine existed), with the engine's EOS/max-new semantics."""
    cache = lm.init_cache(arch, 1, max_len, jnp.float32)
    logits, cache = lm.prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache, arch)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    while len(out) < max_new and (eos_id is None or out[-1] != eos_id):
        logits, cache = lm.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache,
            jnp.int32(pos), arch)
        out.append(int(jnp.argmax(logits[0, -1])))
        pos += 1
    return out


def _prompts(arch, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(1, arch.vocab, l))
            for l in lens]


@pytest.mark.parametrize("name", ARCHS)
def test_continuous_matches_per_request_oracle(name):
    """Staggered admits/retirements, ragged prompt and output lengths,
    an EOS retirement mid-stream, and a mid-decode submit: every
    completion must equal its batch-1 oracle token-for-token."""
    arch = _arch(name)
    params = _params(arch)
    max_len = 24
    lens = [5, 9, 3, 9, 5]
    news = [4, 2, 6, 3, 5]
    prompts = _prompts(arch, lens)

    # force one genuine EOS retirement: request 2's eos_id is a token its
    # unconstrained generation first produces mid-stream (not at step 0)
    free2 = _oracle(params, arch, prompts[2], news[2], max_len)
    eos2 = next((t for i, t in enumerate(free2[1:], 1)
                 if t not in free2[:i]), None)
    eos = [None, None, eos2, None, None]
    want = {i: _oracle(params, arch, prompts[i], news[i], max_len, eos[i])
            for i in range(5)}
    if eos2 is not None:
        assert want[2][-1] == eos2 and len(want[2]) < len(free2) + 1

    engine = ServeEngine(params, arch,
                         ServeConfig(max_batch=2, max_len=max_len))
    engine.warmup(lens)
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=news[i],
                    eos_id=eos[i]) for i in range(5)]
    for r in reqs[:3]:
        engine.submit(r)
    got = []
    for _ in range(2):                     # run a few steps mid-stream...
        got.extend(engine.step())
    for r in reqs[3:]:                     # ...then submit more mid-decode
        engine.submit(r)
    while engine.busy:
        got.extend(engine.step())

    assert {c.uid: c.tokens for c in got} == want
    reasons = {c.uid: c.finish_reason for c in got}
    if eos2 is not None:
        assert reasons[2] == "eos"
    assert all(reasons[i] == "length" for i in (0, 1, 3, 4))
    assert engine.stats["admitted"] == engine.stats["retired"] == 5


def test_static_policy_matches_oracle_with_fewer_steps_than_lockstep():
    """--no-continuous oracle mode: same tokens, but slots only refill
    once the whole pool drains — so it spends more ragged decode steps
    than continuous mode on a mixed-length trace."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    max_len = 24
    lens = [5, 9, 3, 9, 5]
    news = [8, 2, 6, 3, 5]
    prompts = _prompts(arch, lens)
    want = {i: _oracle(params, arch, prompts[i], news[i], max_len)
            for i in range(5)}
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=news[i])
            for i in range(5)]

    steps = {}
    for policy in ("continuous", "static"):
        engine = ServeEngine(params, arch, ServeConfig(
            max_batch=2, max_len=max_len, policy=policy))
        engine.warmup(lens)
        got = engine.run(reqs)
        assert {c.uid: c.tokens for c in got} == want, policy
        steps[policy] = engine.stats["decode_steps"]
    assert steps["continuous"] < steps["static"]


@pytest.mark.parametrize("name", ["llama3_2_1b", "rwkv6_1b6"])
def test_slot_reuse_cannot_leak_state(name):
    """Two requests through the same slot back to back: the second must
    generate exactly what it generates on a fresh engine — covering both
    KV rows (llama) and recurrent mamba/wkv6/shift state (rwkv)."""
    arch = _arch(name)
    params = _params(arch)
    max_len = 20
    pa, pb = _prompts(arch, [8, 8], seed=3)
    want_b = _oracle(params, arch, pb, 5, max_len)

    engine = ServeEngine(params, arch,
                         ServeConfig(max_batch=1, max_len=max_len))
    engine.warmup([8])
    got = engine.run([Request(uid=0, prompt=pa, max_new_tokens=7),
                      Request(uid=1, prompt=pb, max_new_tokens=5)])
    by_uid = {c.uid: c.tokens for c in got}
    assert by_uid[1] == want_b
    assert engine.stats["retired"] == 2


def test_write_slot_overwrites_the_whole_row():
    """The admission write replaces a slot row entirely — stale KV beyond
    the new prompt and stale recurrent state included — and leaves every
    other slot untouched."""
    arch = _arch("jamba_1_5_large")          # kv + conv/ssm state leaves
    dirty = jax.tree.map(
        lambda a: jnp.full_like(a, 7.0), lm.init_cache(arch, 3, 8, jnp.float32))
    row = lm.init_cache(arch, 1, 8, jnp.float32)
    out = write_slot(dirty, row, 1)
    for o, r in zip(jax.tree.leaves(out), jax.tree.leaves(row)):
        np.testing.assert_array_equal(np.asarray(o[:, 1]), np.asarray(r[:, 0]))
        assert np.all(np.asarray(o[:, 0]) == 7.0)
        assert np.all(np.asarray(o[:, 2]) == 7.0)


def test_decode_step_pos_scalar_vs_vector_and_rejection():
    """A scalar pos and a constant (B,) pos produce identical logits; a
    ragged (B,) pos matches per-row scalar decodes; malformed pos shapes
    raise instead of silently mis-RoPE-ing (the old (1, B) broadcast)."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    B, S, max_len = 3, 6, 12
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(1, arch.vocab, (B, S)), jnp.int32)
    cache = lm.init_cache(arch, B, max_len, jnp.float32)
    logits, cache = lm.prefill(params, {"tokens": toks}, cache, arch)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]

    l_scalar, _ = lm.decode_step(params, nxt, cache, jnp.int32(S), arch)
    l_vec, _ = lm.decode_step(params, nxt, cache,
                              jnp.full((B,), S, jnp.int32), arch)
    np.testing.assert_array_equal(np.asarray(l_scalar), np.asarray(l_vec))

    with pytest.raises(ValueError, match="decode pos"):
        lm.decode_step(params, nxt, cache, jnp.zeros((B + 1,), jnp.int32),
                       arch)
    with pytest.raises(ValueError, match="decode pos"):
        lm.decode_step(params, nxt, cache, jnp.zeros((B, 1), jnp.int32),
                       arch)


def test_ragged_positions_match_per_row_references():
    """Slots at *different* depths: assemble a pool from two batch-1
    prefills of different prompt lengths and decode with per-slot
    positions — each row must equal its batch-1 decode to f32 rounding.

    Not bitwise: the batch size is part of the shapes XLA compiles for,
    and the CPU backend tiles and vectorizes a (2, ...) matmul or
    reduction differently from a (1, ...) one, so the same row's f32 sums
    associate in another order and differ in the last bits.  A wrong
    per-slot position or cache row moves the logits by orders of
    magnitude more than this tolerance."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    max_len = 16
    lens = [5, 9]
    prompts = _prompts(arch, lens, seed=2)

    pool = lm.init_cache(arch, 2, max_len, jnp.float32)
    toks, refs = [], []
    for s, p in enumerate(prompts):
        row = lm.init_cache(arch, 1, max_len, jnp.float32)
        logits, row = lm.prefill(
            params, {"tokens": jnp.asarray(p, jnp.int32)[None]}, row, arch)
        tok = int(jnp.argmax(logits[0, -1]))
        lg, _ = lm.decode_step(params, jnp.asarray([[tok]], jnp.int32), row,
                               jnp.int32(lens[s]), arch)
        pool = write_slot(pool, row, s)
        toks.append(tok)
        refs.append(np.asarray(lg[0, -1]))

    lg, _ = lm.decode_step(params, jnp.asarray(toks, jnp.int32)[:, None],
                           pool, jnp.asarray(lens, jnp.int32), arch)
    for b in range(2):
        np.testing.assert_allclose(np.asarray(lg[b, -1]), refs[b],
                                   rtol=1e-5, atol=1e-5)


def test_scheduler_policies_and_validation():
    sched = SlotScheduler(2, "continuous")
    assert sched.admissible(5) == 2
    s0 = sched.admit(Request(uid=0, prompt=(1, 2), max_new_tokens=1))
    assert sched.admissible(5) == 1          # refills a single free slot
    sched.admit(Request(uid=1, prompt=(3,), max_new_tokens=2))
    assert sched.admissible(5) == 0
    sched.retire(s0)
    assert sched.admissible(5) == 1

    static = SlotScheduler(2, "static")
    static.admit(Request(uid=2, prompt=(1,), max_new_tokens=1))
    assert static.admissible(5) == 0         # waits for a full drain
    static.retire(0)
    assert static.admissible(5) == 2

    with pytest.raises(ValueError):
        SlotScheduler(2, "bogus")
    with pytest.raises(ValueError):
        Request(uid=9, prompt=(), max_new_tokens=1)
    with pytest.raises(ValueError):
        Request(uid=9, prompt=(1,), max_new_tokens=0)


@pytest.mark.parametrize("kv_block_size", [0, 4], ids=["dense", "paged"])
def test_warmup_compiles_every_mixed_step_bucket(kv_block_size):
    """``warmup`` must enumerate every step-width bucket the chunked
    engine can hit on the given prompt lengths ({1, chunk} plus the
    greedy per-prompt remainders) — a full staggered trace afterwards
    triggers zero recompiles of the jitted mixed-step fn."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    max_len = 24
    lens = [5, 9, 3]
    prompts = _prompts(arch, lens, seed=4)
    engine = ServeEngine(params, arch, ServeConfig(
        max_batch=2, max_len=max_len, kv_block_size=kv_block_size,
        prefill_chunk_tokens=4))
    engine.warmup(lens)
    compiled = engine._step._cache_size()
    got = engine.run([Request(uid=i, prompt=prompts[i], max_new_tokens=4)
                      for i in range(3)])
    assert len(got) == 3
    assert engine._step._cache_size() == compiled, (
        "mixed-step recompiled during the trace — a step width escaped "
        "warmup's bucket enumeration")


def test_engine_rejects_oversized_and_encdec():
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    engine = ServeEngine(params, arch, ServeConfig(max_batch=1, max_len=8))
    # only a prompt that cannot fit at all is refused; prompt + max_new
    # beyond max_len is served and truncated at the row budget (EOS
    # usually lands earlier — see test_paged_cache for the semantics)
    with pytest.raises(ValueError, match="exceeds the cache row"):
        engine.submit(Request(uid=0, prompt=(1,) * 9, max_new_tokens=1))
    engine.submit(Request(uid=1, prompt=(1,) * 6, max_new_tokens=4))
    with pytest.raises(NotImplementedError):
        ServeEngine({}, C.reduced("seamless_m4t_v2"),
                    ServeConfig(max_batch=1, max_len=8))
