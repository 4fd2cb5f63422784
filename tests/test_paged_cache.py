"""Paged KV cache: token-for-token agreement with the dense slot-pooled
engine across arch families (staggered admits, EOS mid-stream, block
boundary crossings), block free-list hygiene, block-budget admission and
PoolExhausted semantics, the paged kernel's backend agreement, and the
allocated-blocks decode pricing.

The dense engine (``kv_block_size=0``) is the oracle: it is itself
proven token-for-token equal to per-request batch-1 generation by
``test_serve_engine``, so paged == dense here closes the chain.  A tiny
block size (4) forces many boundary crossings — prompts and write
positions land at block_size-1 / block_size / block_size+1.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.kernels import ops
from repro.models import lm
from repro.serve import (BlockAllocator, PoolExhausted, Request,
                         ServeConfig, ServeEngine, SlotScheduler,
                         blocks_for_request, write_slot)

# one arch per family on the serving path: dense GQA attention, MoE,
# RWKV6 recurrence (no KV — paging must degrade to a no-op), Mamba-hybrid
ARCHS = ["llama3_2_1b", "olmoe_1b_7b", "rwkv6_1b6", "jamba_1_5_large"]
BS = 4                      # tiny blocks: every request crosses pages


def _arch(name):
    arch = C.reduced(name)
    if arch.n_experts:
        # high capacity: routing drops would otherwise depend on batch
        # composition and generation could not be batch-size-invariant
        arch = dataclasses.replace(arch, capacity_factor=8.0)
    return arch


def _params(arch):
    return lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)


def _prompts(arch, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(1, arch.vocab, l))
            for l in lens]


def _run(engine, reqs, lens, *, stagger=True):
    engine.warmup(sorted(set(lens)))
    if not stagger:
        return {c.uid: (c.tokens, c.finish_reason)
                for c in engine.run(reqs)}
    for r in reqs[:3]:
        engine.submit(r)
    got = []
    for _ in range(2):                 # run a few steps mid-stream...
        got.extend(engine.step())
    for r in reqs[3:]:                 # ...then submit more mid-decode
        engine.submit(r)
    while engine.busy:
        got.extend(engine.step())
    return {c.uid: (c.tokens, c.finish_reason) for c in got}


@pytest.mark.parametrize("name", ARCHS)
def test_paged_matches_dense_engine(name):
    """Staggered admits, EOS mid-stream, and prompts/positions straddling
    block boundaries (lens 3/4/5 around block_size=4): the paged engine
    must complete every request exactly like the dense engine."""
    arch = _arch(name)
    params = _params(arch)
    max_len = 24
    # prompts at BS-1 / BS / BS+1 plus longer ragged ones; gens long
    # enough that write positions also cross boundaries
    lens = [3, 4, 5, 9, 8]
    news = [6, 5, 7, 3, 5]
    prompts = _prompts(arch, lens)

    dense = ServeEngine(params, arch, ServeConfig(
        max_batch=2, max_len=max_len, kv_block_size=0))
    # pick an EOS the dense engine produces mid-stream for request 2
    free2 = _run(ServeEngine(params, arch, ServeConfig(
                     max_batch=1, max_len=max_len, kv_block_size=0)),
                 [Request(uid=2, prompt=prompts[2], max_new_tokens=news[2])],
                 [lens[2]], stagger=False)[2][0]
    eos2 = next((t for i, t in enumerate(free2[1:], 1)
                 if t not in free2[:i]), None)
    eos = [None, None, eos2, None, None]
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=news[i],
                    eos_id=eos[i]) for i in range(5)]
    want = _run(dense, reqs, lens)

    paged = ServeEngine(params, arch, ServeConfig(
        max_batch=2, max_len=max_len, kv_block_size=BS))
    got = _run(paged, reqs, lens)
    assert got == want
    if eos2 is not None:
        assert got[2][1] == "eos"
    if paged.paged:
        assert paged.peak_blocks_in_use > 0
        # the pool holds max_batch x pages usable blocks plus the trash
        # block, against the dense layout's max_batch x max_len rows
        pages = -(-max_len // BS)
        assert (paged.kv_bytes_reserved * 2 * max_len
                == dense.kv_bytes_reserved * (2 * pages + 1) * BS)
    else:
        assert name == "rwkv6_1b6"     # no KV leaves -> paging no-op
        assert paged.kv_bytes_reserved == dense.kv_bytes_reserved == 0


def test_block_free_list_restored_after_retires():
    """Retire N requests through a small slot pool: every block is
    accounted for — back on the free list, or (default "lru" prefix
    retention) held by the prefix index and returned in full by
    ``flush()`` — and every table row points back at the trash block.
    A leak here would strangle a long-running server."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    engine = ServeEngine(params, arch, ServeConfig(
        max_batch=2, max_len=20, kv_block_size=BS))
    lens = [3, 7, 5, 9, 4, 6]
    prompts = _prompts(arch, lens, seed=5)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    engine.warmup(sorted(set(lens)))
    done = engine.run(reqs)
    assert len(done) == len(reqs)
    alloc = engine._alloc
    usable = alloc.num_blocks - 1
    # retained prompt blocks are not leaked: the index owns them and
    # hands every one back on flush
    retained = engine.prefix.flush()
    assert retained > 0                        # prompts published blocks
    assert alloc.free_blocks == usable
    assert (alloc.tables == 0).all()
    assert alloc.peak_in_use > 0
    assert engine.scheduler.reserved_blocks == 0
    assert alloc.pinned_shared == 0


def test_submit_truncates_instead_of_rejecting_and_raises_pool_exhausted():
    """The old engine refused prompt+max_new > max_len outright even
    though EOS usually lands earlier; now generation truncates at the
    row budget (token-for-token with the dense engine), only a prompt
    that cannot fit at all is a ValueError, and a request whose worst-
    case block need exceeds the whole pool raises PoolExhausted."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    max_len = 10
    (p8,) = _prompts(arch, [8], seed=7)

    outs = {}
    for bs in (0, BS):
        engine = ServeEngine(params, arch, ServeConfig(
            max_batch=1, max_len=max_len, kv_block_size=bs))
        engine.warmup([8])
        # prompt 8 + max_new 99 >> max_len 10: admitted, truncated
        (c,) = engine.run([Request(uid=0, prompt=p8, max_new_tokens=99)])
        assert c.finish_reason == "length"
        assert len(c.tokens) == max_len - len(p8) + 1
        outs[bs] = c.tokens
        if not bs:
            dense_bytes = engine.kv_bytes_reserved
        with pytest.raises(ValueError, match="exceeds the cache row"):
            engine.submit(Request(uid=1, prompt=(1,) * (max_len + 1),
                                  max_new_tokens=1))
    assert outs[0] == outs[BS]

    # a pool too small for the request's worst case can never serve it
    small = ServeEngine(params, arch, ServeConfig(
        max_batch=1, max_len=max_len, kv_block_size=BS, kv_pool_blocks=1))
    with pytest.raises(PoolExhausted, match="KV blocks worst-case"):
        small.submit(Request(uid=2, prompt=p8, max_new_tokens=99))
    # a bounded pool reserves its usable blocks plus the trash block, a
    # share of the dense row's bytes that does not depend on the traffic
    assert small.kv_bytes_reserved * max_len == dense_bytes * (1 + 1) * BS


def test_scheduler_admits_on_blocks_not_slots():
    """Block-budget admission: many short requests coexist where few
    long ones fit, FCFS order is preserved (a long head request is not
    starved by short ones behind it), and retiring releases the
    reservation."""
    sched = SlotScheduler(4, "continuous", block_size=8, total_blocks=4,
                          max_len=64)
    short = [Request(uid=i, prompt=(1,) * 4, max_new_tokens=4)
             for i in range(6)]                       # 1 block each
    long = [Request(uid=10 + i, prompt=(1,) * 20, max_new_tokens=10)
            for i in range(3)]                        # 4 blocks each
    assert sched.blocks_for(short[0]) == 1
    assert sched.blocks_for(long[0]) == blocks_for_request(20, 10, 64, 8) == 4

    assert sched.admissible_requests(short) == 4      # slot-limited
    assert sched.admissible_requests(long) == 1       # block-limited
    assert sched.admissible_requests([long[0]] + short) == 1  # FCFS stop

    s = sched.admit(long[0])
    assert sched.free_block_budget == 0
    assert sched.admissible_requests(short) == 0      # budget exhausted
    sched.retire(s)
    assert sched.free_block_budget == 4
    for r in short[:4]:
        sched.admit(r)
    assert sched.free_block_budget == 0 and not sched.free_slots()


def test_block_allocator_lazy_alloc_and_trash_block():
    alloc = BlockAllocator(6, 4, max_batch=2, pages_per_slot=4)
    assert alloc.free_blocks == 5 and alloc.blocks_in_use == 0
    alloc.ensure(0, 0)                                # page 0 bound
    assert alloc.blocks_in_use == 1
    assert alloc.ensure(0, 3) is None                 # same page (pos 3)
    assert alloc.blocks_in_use == 1
    alloc.ensure(0, 4)                                # boundary crossing
    assert alloc.blocks_in_use == 2
    assert alloc.tables[0, 0] != 0 and alloc.tables[0, 1] != 0
    assert (alloc.tables[1] == 0).all()               # other slot: trash
    with pytest.raises(ValueError):
        alloc.alloc(0, 0)                             # double-bind
    assert alloc.free_slot(0) == 2
    assert alloc.free_blocks == 5 and (alloc.tables == 0).all()
    assert alloc.peak_in_use == 2
    with pytest.raises(ValueError):
        BlockAllocator(1, 4, max_batch=1, pages_per_slot=1)


def test_paged_write_slot_overwrites_prompt_blocks_and_state_row():
    """Admission must fully overwrite every prompt block and the slot's
    recurrent-state row, and touch nothing else — the paged analogue of
    the dense full-row-overwrite hygiene guarantee (one unified
    ``write_slot`` signature: ``block_ids`` switches the KV layout)."""
    arch = _arch("jamba_1_5_large")          # kv + conv/ssm state leaves
    nb, bs = 2, 4
    pool = jax.tree.map(lambda a: jnp.full_like(a, 7.0),
                        lm.init_paged_cache(arch, 6, bs, 3, jnp.float32))
    row = lm.init_cache(arch, 1, nb * bs, jnp.float32)
    ids = jnp.asarray([2, 5], jnp.int32)
    out = write_slot(pool, row, 1, block_ids=ids)
    flat_out = jax.tree_util.tree_flatten_with_path(out)[0]
    flat_row = jax.tree.leaves(row)
    assert len(flat_out) == len(flat_row)
    for (path, o), r in zip(flat_out, flat_row):
        is_kv = any(getattr(k, "key", None) == "kv" for k in path)
        o, r = np.asarray(o), np.asarray(r)
        if is_kv:
            n = o.shape[0]
            want = r[:, 0].reshape(n, nb, bs, *o.shape[3:])
            np.testing.assert_array_equal(o[:, [2, 5]], want)
            for b in (0, 1, 3, 4):               # untouched blocks
                assert np.all(o[:, b] == 7.0), path
        else:
            np.testing.assert_array_equal(o[:, 1], r[:, 0])
            assert np.all(o[:, 0] == 7.0) and np.all(o[:, 2] == 7.0)


def test_paged_kernel_backends_agree():
    """The scalar-prefetch Pallas kernel (interpret) must match the
    gather oracle bit-for-bit-ish on ragged lengths and scrambled block
    tables, scalar and per-slot kv_len forms both."""
    rng = np.random.default_rng(0)
    B, KH, G, D, NB, bs, pages = 3, 2, 4, 32, 12, 8, 4
    q = jnp.asarray(rng.normal(size=(B, KH, G, D)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, bs, KH, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, bs, KH, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(NB)[:B * pages].reshape(B, pages),
                     jnp.int32)
    for kv_len in (jnp.asarray([1, 17, 31], jnp.int32), jnp.int32(9)):
        r = ops.paged_decode_attention(q, kp, vp, bt, kv_len, backend="ref")
        i = ops.paged_decode_attention(q, kp, vp, bt, kv_len,
                                       backend="interpret")
        np.testing.assert_allclose(np.asarray(r), np.asarray(i),
                                   rtol=2e-6, atol=2e-6)


def test_decode_phase_prices_allocated_blocks_not_max_len():
    """phase_shape(kv_tokens=...) must shrink the decode graph's cache
    depth (the dominant kv_bytes term) to the paged budget, and the
    serve-plan resolver must record the block-rounded depth."""
    from repro.models.graph_export import export_graph, phase_shape

    arch = _arch("llama3_2_1b")
    padded = phase_shape("decode", seq_len=2048, batch=8)
    paged = phase_shape("decode", seq_len=2048, batch=8, kv_tokens=640)
    assert (padded.seq_len, paged.seq_len) == (2048, 640)
    assert paged.kind == "decode" and paged.global_batch == 8
    # kv_tokens can never price above the reservation
    assert phase_shape("decode", seq_len=512, batch=8,
                       kv_tokens=4096).seq_len == 512
    kvb = {s.seq_len: export_graph(arch, s).nodes["L0.attn"].extra["kv_bytes"]
           for s in (padded, paged)}
    assert kvb[640] == pytest.approx(kvb[2048] * 640 / 2048)

    # the serve resolver's block rounding: a 512+39-token worst case on
    # 128-token blocks prices a 640-deep cache, not the 2048 reservation
    assert blocks_for_request(512, 39, 2048, 128) * 128 == 640


SHARDED = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
import numpy as np
from repro import compat, configs as C
from repro.core import AxisSpec, ICI_BW, MeshSpec
from repro.core.sharding import use_mesh
from repro.models import lm
from repro.plans import build_parallel_plan
from repro.serve import Request, ServeConfig, ServeEngine

arch = C.reduced("llama3_2_1b")
mesh_spec = MeshSpec(axes=(AxisSpec("data", 4, ICI_BW),
                           AxisSpec("model", 2, ICI_BW)))
max_len = 24
pp = build_parallel_plan(arch, mesh_spec, strategy="searched",
                         phases=("prefill", "decode"), prompt_len=8,
                         max_batch=4, max_len=max_len, decode_kv_tokens=16)

params = lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)
rng = np.random.default_rng(3)
lens = [5, 8, 3, 8, 5]
prompts = [tuple(int(t) for t in rng.integers(1, arch.vocab, l))
           for l in lens]
reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=4)
        for i in range(len(lens))]

# dense single-device oracle
oracle = ServeEngine(params, arch, ServeConfig(max_batch=4, max_len=max_len,
                                               kv_block_size=0))
oracle.warmup(sorted(set(lens)))
want = {c.uid: c.tokens for c in oracle.run(reqs)}

# paged engine under the searched decode plan on the real 8-device mesh
mesh = compat.make_mesh((4, 2), ("data", "model"))
with use_mesh(mesh):
    engine = ServeEngine(params, arch,
                         ServeConfig(max_batch=4, max_len=max_len,
                                     kv_block_size=4), plan=pp)
    engine.warmup(sorted(set(lens)))
    got = {c.uid: c.tokens for c in engine.run(reqs)}
assert engine.paged, "paged engine expected"
assert got == want, (got, want)

# the block pool itself is laid out by the decode-phase plan: at least
# one *KV pool* leaf spans more than one device
kv_spans = [len(leaf.sharding.device_set)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                engine.cache)[0]
            if any(getattr(k, "key", None) == "kv" for k in path)]
assert kv_spans and max(kv_spans) > 1, kv_spans
print("OK paged-pool-span=" + str(max(kv_spans)))
"""


@pytest.mark.slow
def test_searched_decode_plan_shards_the_paged_pool():
    """8 virtual devices: a searched decode-phase plan must lay the
    paged block pool out across the mesh (heads sharded, blocks
    replicated) while generation stays token-for-token equal to the
    dense single-device oracle."""
    import subprocess
    import sys

    r = subprocess.run([sys.executable, "-c", SHARDED],
                       capture_output=True, text=True, timeout=1200,
                       cwd=".")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout
