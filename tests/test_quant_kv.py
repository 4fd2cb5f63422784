"""Int8-quantized paged KV blocks: numerics, copy-on-write, backend
agreement, config validation, and end-to-end pricing.

The quantization contract is *bounded noise, zero structure change*:

* Numerics — a teacher-forced probe (dense fp cache vs int8 paged pool
  on an identity block table, so both see the same logical KV) must stay
  inside a per-family logit tolerance.  The bounds are documented
  measurements (max |logit delta| on the reduced archs: llama ~6e-3,
  olmoe ~5e-2, jamba ~0.65 — the mamba recurrence integrates the noise
  over the stream) with ~15x headroom.  Exact token identity is NOT the
  contract for attention archs: fp top-2 logit margins can be smaller
  than the quantization delta, so argmax agreement is seed luck.  For
  the attention-free rwkv6 family quantization is structurally inert —
  no kv leaves exist to quantize — and equality is exact.
* Scheduling — the int8 engine must survive the same staggered-admit /
  mid-stream-EOS / block-crossing trace the mixed-step suite runs, while
  physically reserving fewer KV bytes than the fp pool (int8 payload +
  f32 scales = 0.25 + 1/head_dim of an f32 pool).
* COW — ``copy_block`` must treat payload and scale rows as a unit: a
  divergent copy that moved int8 payload under the *old* scales would
  silently rescale history.
* Backends — ref / chunked-XLA / Pallas-interpret must agree on the
  dequantizing gather.
* Pricing — the searched decode plan must *see* the narrower cache read:
  ``kv_quant`` flows ShapeSpec -> graph export -> cost model -> plan
  meta -> plan JSON.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.device import AxisSpec, ICI_BW, MeshSpec
from repro.kernels import ops
from repro.kernels.quant import dequantize_kv, quantize_kv
from repro.models import lm
from repro.models.graph_export import A_BYTES, export_graph, phase_shape
from repro.serve import Request, ServeConfig, ServeEngine
from repro.serve.engine import copy_block

ARCHS = ["llama3_2_1b", "olmoe_1b_7b", "rwkv6_1b6", "jamba_1_5_large"]

#: measured max |logit delta| of the teacher-forced probe on these
#: reduced archs (llama 6.4e-3, olmoe 2.4, jamba 6.5e-1), with headroom
#: for float-library drift.  jamba's bound is large because the mamba
#: recurrence accumulates the per-step quantization noise; olmoe's is
#: larger still because top-k expert routing is discontinuous — a tiny
#: KV perturbation can flip a near-tied router decision and swap whole
#: expert FFNs, so the bound only asserts the output stays on the scale
#: of one expert's contribution rather than diverging.
TOL = {"llama3_2_1b": 0.1, "olmoe_1b_7b": 4.0, "jamba_1_5_large": 2.0,
       "rwkv6_1b6": 0.0}


def _arch(name):
    arch = C.reduced(name)
    if arch.n_experts:
        arch = dataclasses.replace(arch, capacity_factor=8.0)
    return arch


def _params(arch):
    return lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)


def _prompts(arch, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(t) for t in rng.integers(1, arch.vocab, l))
            for l in lens]


# --------------------------------------------------------------------- #
# quantize/dequantize primitive
# --------------------------------------------------------------------- #

def test_quantize_roundtrip_error_bound():
    """Per-row symmetric absmax int8: the roundtrip error of every
    element is at most scale/2 = absmax/254."""
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 3, 16)) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8
    assert s.shape == x.shape[:-1]
    err = jnp.abs(dequantize_kv(q, s) - x)
    bound = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0 + 1e-7
    assert bool(jnp.all(err <= bound))


def test_quantize_zero_rows_are_exact():
    """All-zero rows produce scale 0 and dequantize back to exactly 0
    (the divisor guard must not emit NaN)."""
    x = jnp.zeros((2, 5, 4))
    q, s = quantize_kv(x)
    assert bool(jnp.all(s == 0.0))
    assert bool(jnp.all(dequantize_kv(q, s) == 0.0))


# --------------------------------------------------------------------- #
# numerics: teacher-forced probe, per family
# --------------------------------------------------------------------- #

def _probe_delta(name, tokens=24, block_size=8):
    arch = _arch(name)
    params = _params(arch)
    rng = np.random.default_rng(7)
    toks = rng.integers(1, arch.vocab, tokens)
    pages = -(-tokens // block_size)
    dense = lm.init_cache(arch, 1, pages * block_size, jnp.float32)
    quant = lm.init_paged_cache(arch, pages + 1, block_size, 1,
                                jnp.float32, kv_quant="int8")
    bt = jnp.arange(1, pages + 1, dtype=jnp.int32)[None, :]
    delta = 0.0
    for i, t in enumerate(toks):
        tok = jnp.full((1, 1), int(t), jnp.int32)
        pos = jnp.full((1,), i, jnp.int32)
        ld, dense = lm.decode_step(params, tok, dense, pos, arch)
        lq, quant = lm.decode_step(params, tok, quant, pos, arch,
                                   block_tables=bt)
        delta = max(delta, float(jnp.max(jnp.abs(ld - lq))))
    return delta, quant


@pytest.mark.parametrize("name", ARCHS)
def test_int8_probe_within_documented_tolerance(name):
    delta, quant = _probe_delta(name)
    assert delta <= TOL[name], (
        f"{name}: int8 logit delta {delta} above documented bound "
        f"{TOL[name]}")
    if name == "rwkv6_1b6":
        # attention-free: no kv leaves exist, so nothing was quantized
        # and agreement is exact — also prove no int8/scale leaf appeared
        leaves = jax.tree_util.tree_leaves_with_path(quant)
        assert delta == 0.0
        assert not any(leaf.dtype == jnp.int8 for _, leaf in leaves)
        assert not any(getattr(p[-1], "key", None) in
                       ("k_scale", "v_scale") for p, _ in leaves)
    else:
        assert delta > 0.0            # the attention archs really quantized


def test_int8_pool_layout():
    """The quantized pool stores int8 K/V plus f32 per-(slot, head)
    scales inside the kv subtree, zero-initialized (block 0 — the trash
    block — dequantizes to exactly 0)."""
    arch = _arch("llama3_2_1b")
    cache = lm.init_paged_cache(arch, 6, 8, 2, jnp.float32,
                                kv_quant="int8")
    kv = cache["l0"]["kv"]
    assert kv["k"].dtype == jnp.int8 and kv["v"].dtype == jnp.int8
    assert kv["k_scale"].dtype == jnp.float32
    assert kv["k_scale"].shape == kv["k"].shape[:-1]
    assert bool(jnp.all(kv["k_scale"] == 0.0))
    with pytest.raises(ValueError):
        lm.init_paged_cache(arch, 6, 8, 2, jnp.float32, kv_quant="int4")


# --------------------------------------------------------------------- #
# engine: the staggered trace runs green on the int8 pool
# --------------------------------------------------------------------- #

def _engine_run(params, arch, reqs, lens, *, kv_quant, chunk=4,
                block_size=4, max_len=24):
    engine = ServeEngine(params, arch, ServeConfig(
        max_batch=2, max_len=max_len, kv_block_size=block_size,
        prefill_chunk_tokens=chunk, kv_quant=kv_quant))
    engine.warmup(lens)
    for r in reqs[:3]:
        engine.submit(r)
    got = []
    for _ in range(2):
        got.extend(engine.step())
    for r in reqs[3:]:
        engine.submit(r)
    while engine.busy:
        got.extend(engine.step())
    assert engine.stats["retired"] == len(reqs)
    return engine, {c.uid: (c.tokens, c.finish_reason) for c in got}


@pytest.mark.parametrize("name", ARCHS)
def test_int8_engine_staggered_trace(name):
    """Staggered admits, a mid-decode submit, prompts crossing block
    boundaries (lens 3..9 against block_size 4): the int8 engine must
    retire everything, respect max_new_tokens, and — for attention
    archs — reserve strictly fewer KV bytes than the fp pool.  rwkv6
    (no attention -> quantization inert) must match the fp engine
    token for token."""
    arch = _arch(name)
    params = _params(arch)
    lens = [5, 9, 3, 9, 5]
    news = [4, 2, 6, 3, 5]
    prompts = _prompts(arch, lens)
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=news[i])
            for i in range(5)]

    efp, fp = _engine_run(params, arch, reqs, lens, kv_quant=None)
    eq, q8 = _engine_run(params, arch, reqs, lens, kv_quant="int8")

    assert set(q8) == set(fp)
    for uid, (toks, reason) in q8.items():
        assert 0 < len(toks) <= news[uid]
    if name == "rwkv6_1b6":
        assert q8 == fp
        assert eq.kv_bytes_reserved == efp.kv_bytes_reserved == 0
    else:
        # int8 payload (0.25x of f32) + f32 scales (1/hd per element)
        frac = eq.kv_bytes_reserved / efp.kv_bytes_reserved
        assert abs(frac - (0.25 + 1.0 / arch.hd)) < 1e-6


def test_int8_engine_mid_stream_eos():
    """A genuine mid-stream EOS retirement on the int8 engine: eos_id is
    a token the int8 engine's own free-running generation produces after
    step 0, so retirement is exercised on the quantized path itself."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    lens = [5, 7, 3]
    prompts = _prompts(arch, lens)
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=8)
            for i in range(3)]
    _, free = _engine_run(params, arch, reqs, lens, kv_quant="int8")
    toks0 = free[0][0]
    eos = next((t for i, t in enumerate(toks0[1:], 1)
                if t not in toks0[:i]), None)
    assert eos is not None
    reqs[0] = dataclasses.replace(reqs[0], eos_id=eos)
    _, got = _engine_run(params, arch, reqs, lens, kv_quant="int8")
    assert got[0][1] == "eos"
    assert len(got[0][0]) < 8


# --------------------------------------------------------------------- #
# copy-on-write: payload and scales move as a unit
# --------------------------------------------------------------------- #

def test_copy_block_copies_payload_and_scales_together():
    arch = _arch("llama3_2_1b")
    cache = lm.init_paged_cache(arch, 6, 8, 2, jnp.float32,
                                kv_quant="int8")
    kv = cache["l0"]["kv"]
    src, dst = 2, 4
    kv["k"] = kv["k"].at[:, src].set(
        jnp.arange(kv["k"][:, src].size, dtype=jnp.int8).reshape(
            kv["k"][:, src].shape) % 100)
    kv["k_scale"] = kv["k_scale"].at[:, src].set(0.5)
    kv["v_scale"] = kv["v_scale"].at[:, src].set(0.25)
    state_before = jax.tree.map(
        lambda x: x, {k: v for k, v in cache["l0"].items() if k != "kv"})

    out = copy_block(cache, src, dst)
    okv = out["l0"]["kv"]
    assert bool(jnp.all(okv["k"][:, dst] == kv["k"][:, src]))
    assert bool(jnp.all(okv["k_scale"][:, dst] == 0.5))
    assert bool(jnp.all(okv["v_scale"][:, dst] == 0.25))
    # the source stays intact and non-kv state is untouched
    assert bool(jnp.all(okv["k"][:, src] == kv["k"][:, src]))
    for k, v in state_before.items():
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool(jnp.all(a == b)), v, out["l0"][k]))


def test_prefix_cache_cow_under_int8():
    """Two requests sharing a whole-block prefix on the int8 pool: the
    prefix cache must hit, and post-divergence generations must match a
    sharing-off int8 engine exactly (COW isolation is bit-exact — both
    engines read identically-quantized blocks)."""
    arch = _arch("llama3_2_1b")
    params = _params(arch)
    bs = 4
    shared = _prompts(arch, [bs * 2])[0]          # two whole shared blocks
    tails = _prompts(arch, [3, 5], seed=1)
    prompts = [shared + tails[0], shared + tails[1]]
    lens = sorted({len(p) for p in prompts})

    def run(prefix_cache):
        engine = ServeEngine(params, arch, ServeConfig(
            max_batch=2, max_len=32, kv_block_size=bs,
            prefill_chunk_tokens=bs, kv_quant="int8",
            prefix_cache=prefix_cache))
        engine.warmup(lens)
        for i, p in enumerate(prompts):
            engine.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        got = []
        while engine.busy:
            got.extend(engine.step())
        return engine, {c.uid: c.tokens for c in got}

    e_on, on = run(True)
    e_off, off = run(False)
    assert on == off
    assert e_on.prefix_hit_rate > 0.0
    assert e_on.prefill_tokens_saved > 0


# --------------------------------------------------------------------- #
# backend agreement on the dequantizing gather
# --------------------------------------------------------------------- #

def test_paged_decode_backends_agree_on_int8():
    B, KH, G, D = 3, 2, 4, 16
    NB, bs, pages = 9, 8, 3
    key = jax.random.PRNGKey(0)
    kq, ks, kv_, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, KH, G, D))
    k_fp = jax.random.normal(kp, (NB, bs, KH, D)) * 2.0
    v_fp = jax.random.normal(kv_, (NB, bs, KH, D)) * 2.0
    k_pool, k_scale = quantize_kv(k_fp)
    v_pool, v_scale = quantize_kv(v_fp)
    bt = jax.random.randint(ks, (B, pages), 1, NB)
    kv_len = jnp.asarray([bs * pages, 5, 11], jnp.int32)

    outs = {}
    for backend in ("ref", "xla", "interpret"):
        outs[backend] = ops.paged_decode_attention(
            q, k_pool, v_pool, bt, kv_len, k_scale=k_scale,
            v_scale=v_scale, backend=backend)
    np.testing.assert_allclose(outs["ref"], outs["xla"],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(outs["ref"], outs["interpret"],
                               rtol=2e-5, atol=2e-5)
    # and the dequantizing gather matches fp attention over the
    # dequantized pools exactly (the kernel must apply the same scales)
    want = ops.paged_decode_attention(
        q, dequantize_kv(k_pool, k_scale), dequantize_kv(v_pool, v_scale),
        bt, kv_len, backend="ref")
    np.testing.assert_allclose(outs["ref"], want, rtol=2e-5, atol=2e-5)


def test_paged_decode_rejects_mismatched_scales():
    """One scale without the other (or a shape mismatch) must fail
    dispatch loudly, not silently run fp attention on int8 payload."""
    B, KH, G, D, NB, bs = 1, 2, 1, 8, 4, 4
    q = jnp.zeros((B, KH, G, D))
    pool = jnp.zeros((NB, bs, KH, D), jnp.int8)
    scale = jnp.zeros((NB, bs, KH))
    bt = jnp.zeros((B, 2), jnp.int32)
    kv_len = jnp.asarray([4], jnp.int32)
    with pytest.raises(Exception):
        ops.paged_decode_attention(q, pool, pool, bt, kv_len,
                                   k_scale=scale, backend="ref")
    with pytest.raises(Exception):
        ops.paged_decode_attention(q, pool, pool, bt, kv_len,
                                   k_scale=scale,
                                   v_scale=scale[:, :1], backend="ref")


# --------------------------------------------------------------------- #
# config validation
# --------------------------------------------------------------------- #

def test_serve_config_validates_kv_quant():
    ok = ServeConfig(max_batch=1, max_len=8, kv_block_size=4,
                     kv_quant="int8")
    assert ok.kv_quant == "int8"
    ServeConfig(max_batch=1, max_len=8, kv_block_size=0, kv_quant="none")
    ServeConfig(max_batch=1, max_len=8, kv_block_size=0, kv_quant=None)
    with pytest.raises(ValueError, match="kv_quant"):
        ServeConfig(max_batch=1, max_len=8, kv_block_size=4,
                    kv_quant="fp8")
    with pytest.raises(ValueError, match="paged"):
        ServeConfig(max_batch=1, max_len=8, kv_block_size=0,
                    kv_quant="int8")


# --------------------------------------------------------------------- #
# pricing: the cost model sees the narrower cache read
# --------------------------------------------------------------------- #

def test_phase_shape_records_kv_quant():
    s = phase_shape("decode", seq_len=512, batch=4, kv_quant="int8")
    assert s.kv_quant == "int8" and s.name.endswith("+int8")
    s2 = phase_shape("decode", seq_len=512, batch=4, kv_quant="none")
    assert s2.kv_quant is None and "+int8" not in s2.name
    # non-decode phases never carry it
    assert phase_shape("prefill", seq_len=512, batch=4).kv_quant is None


def test_graph_export_prices_int8_cache_read():
    arch = _arch("llama3_2_1b")
    g_fp = export_graph(arch, phase_shape("decode", seq_len=512, batch=4))
    g_q = export_graph(arch, phase_shape("decode", seq_len=512, batch=4,
                                         kv_quant="int8"))
    attn = [n for n in g_fp.nodes if n.endswith(".attn")]
    assert attn
    for n in attn:
        fp_kv = g_fp.nodes[n].extra["kv_bytes"]
        q_kv = g_q.nodes[n].extra["kv_bytes"]
        # fp prices A_BYTES per element (f32: 4); int8 prices 1 + 4/hd
        assert q_kv == pytest.approx(
            fp_kv * (1.0 + 4.0 / arch.hd) / A_BYTES)
    # prefill graphs are untouched by kv_quant (quantize-on-write only
    # narrows the decode-time cache read)
    p_fp = export_graph(arch, phase_shape("prefill", seq_len=512, batch=4))
    p_q = export_graph(
        arch, phase_shape("prefill", seq_len=512, batch=4,
                          kv_quant="int8"))
    for n in p_fp.nodes:
        assert p_fp.nodes[n].extra.get("kv_bytes") == \
            p_q.nodes[n].extra.get("kv_bytes")


def test_searched_decode_plan_shifts_under_int8_pricing():
    """On a 4x2 mesh with an MQA variant (n_kv_heads=1, so the model
    axis cannot hide in head sharding) the int8-priced decode search
    must return a strictly cheaper cost AND a different assignment than
    the fp-priced search — the quantized cache read genuinely changes
    the plan, not just its price tag: the mixed step of 4 slots at 8
    query tokens splits the f32 cache's sequence over ``model`` and
    stops splitting the int8 cache, whose read no longer pays for it."""
    from repro.core.search import find_strategy
    from repro.launch.train import reduced_arch

    arch = dataclasses.replace(
        reduced_arch(C.get("llama3.2-1b"), 1024, 4, 512, 4), n_kv_heads=1)
    mesh = MeshSpec(axes=(AxisSpec("data", 4, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    strat = {}
    for kvq in (None, "int8"):
        shape = phase_shape("decode", seq_len=2048, batch=4,
                            kv_tokens=2048, q_tokens=8, kv_quant=kvq)
        strat[kvq] = find_strategy(export_graph(arch, shape), mesh,
                                   phase="decode")
    assert strat["int8"].cost < strat[None].cost
    assert strat["int8"].assignment != strat[None].assignment
    attn = [n for n in strat[None].assignment if n.endswith(".attn")]
    assert all(strat[None][n].axes_for("seq") for n in attn)
    assert not any(strat["int8"][n].axes_for("seq") for n in attn)


def test_plan_meta_records_and_roundtrips_kv_quant(tmp_path):
    from repro.plans import build_parallel_plan, ParallelPlan

    arch = _arch("llama3_2_1b")
    mesh = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    plan = build_parallel_plan(
        arch, mesh, strategy="searched", phases=("decode",),
        max_batch=4, max_len=256, decode_kv_quant="int8")
    assert plan.meta["kv_quant"] == "int8"
    assert plan.meta["phases"]["decode"]["shape"]["kv_quant"] == "int8"

    path = tmp_path / "plan.json"
    plan.save(str(path))
    loaded = ParallelPlan.load(str(path), arch=arch)
    assert loaded.meta["kv_quant"] == "int8"
    # absent field = fp: a pre-quantization plan file loads clean
    raw = json.loads(path.read_text())
    raw["meta"].pop("kv_quant")
    path.write_text(json.dumps(raw))
    legacy = ParallelPlan.load(str(path), arch=arch)
    assert legacy.meta.get("kv_quant") is None

    fp_plan = build_parallel_plan(
        arch, mesh, strategy="searched", phases=("decode",),
        max_batch=4, max_len=256)
    assert "kv_quant" not in fp_plan.meta


def test_resolve_serve_plan_threads_kv_quant():
    from repro.launch.serve import resolve_serve_plan

    arch = _arch("llama3_2_1b")
    mesh = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    plan = resolve_serve_plan(
        arch, mesh, strategy="searched", prompt_len=64, max_batch=2,
        max_len=128, kv_block_size=16, kv_quant="int8")
    assert plan.meta["kv_quant"] == "int8"
    # dense rows cannot quantize: the knob must not leak into pricing
    dense = resolve_serve_plan(
        arch, mesh, strategy="searched", prompt_len=64, max_batch=2,
        max_len=128, kv_block_size=0, kv_quant="int8")
    assert "kv_quant" not in dense.meta
