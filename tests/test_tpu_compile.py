"""Every Pallas kernel compiles with Mosaic for a TPU v5e at published
widths.

Nothing runs: each kernel (and, for training, its backward) is lowered and compiled for one chip of a *described* v5e:2x2
topology, so Mosaic refuses here what it would refuse on the chip —
unaligned block shapes, unsupported relayouts, too much VMEM — while the
rest of the suite runs the same kernels in interpret mode on the CPU.

The topology is described inside a module fixture (never at import): the
TPU compiler library is process-exclusive, and describing it while
pytest-xdist workers import this file would make the workers collect
different tests.  The persistent compilation cache stays off around the
compiles: an entry compiled for a described chip cannot be read back
without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.kernels import dispatch
from repro.models.moe import capacity


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topology):
    return jax.sharding.SingleDeviceSharding(topology.devices[0])


def _pallas(op):
    return dispatch.backends(op)["pallas"].fn


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32 = jnp.float32, jnp.int32
OLMO = configs.get("olmo-1b")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_train_step_compiles(one_chip, dtype):
    """olmo-1b training attention: seq 2048, batch 4, the drivers'
    query chunk 256; forward kernel plus the Pallas backward."""
    B, S, H, D = 4, 2048, OLMO.n_heads, OLMO.hd
    fwd = _pallas("flash_attention")

    def loss(q, k, v):
        return fwd(q, k, v, causal=True, block_q=256).astype(F32).sum()

    shape = ((B, H, S, D), dtype)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, shape, shape, shape)


def test_flash_attention_backward_compiles(one_chip):
    """granite-3-2b training attention as the benchmark's cell runs it
    (a microbatch of 2, GQA 32/8 of head 64, seq 2048, f32, query chunk
    512): the gradient runs the two backward kernels, keeps no XLA chunk
    loop, and only the forward's calls have the single (B, H, S, D)
    output by which the trace's roofline reader finds them."""
    arch = configs.get("granite-3-2b")
    B, S, H, KH, D = 2, 2048, arch.n_heads, arch.n_kv_heads, arch.hd
    fwd = _pallas("flash_attention")

    def loss(q, k, v):
        return fwd(q, k, v, causal=True, block_q=512).sum()

    text = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((B, H, S, D), F32), ((B, KH, S, D), F32),
                    ((B, KH, S, D), F32)).as_text()
    assert "flash_attention_bwd_dq" in text
    assert "flash_attention_bwd_dkv" in text
    assert "while" not in text
    single = re.compile(rf"= \w+\[{B},{H},{S},{D}\]\S* custom-call\(.*"
                        r"tpu_custom_call")
    calls = [ln for ln in text.splitlines() if single.search(ln)]
    assert calls and all("flash_attention_fwd" in ln.split(" = ")[0]
                         for ln in calls), calls


def test_decode_attention_compiles(one_chip):
    """olmo-1b dense-cache decode: 8 slots over a 4096-token cache."""
    B, T, KH, D = 8, 4096, OLMO.n_kv_heads, OLMO.hd
    G = OLMO.n_heads // KH
    _compile(_pallas("decode_attention"), one_chip, ((B, KH, G, D), F32),
             ((B, KH, T, D), F32), ((B, KH, T, D), F32), ((B,), I32))


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_attention_compiles(one_chip, quant):
    """olmo-1b paged decode as the serve engine runs it: 8 slots, block
    size 128, 5 pages per slot, fp or int8 pool with f32 scales."""
    B, pages, bs, KH, D = 8, 5, 128, OLMO.n_kv_heads, OLMO.hd
    NB, G = B * pages + 1, OLMO.n_heads // KH
    pool = ((NB, bs, KH, D), jnp.int8 if quant else F32)
    shapes = [((B, KH, G, D), F32), pool, pool, ((B, pages), I32),
              ((B,), I32)]
    fn = _pallas("paged_decode_attention")
    if quant:
        shapes += [((NB, bs, KH), F32)] * 2
        _compile(lambda q, k, v, bt, n, ks, vs: fn(
            q, k, v, bt, n, k_scale=ks, v_scale=vs), one_chip, *shapes)
    else:
        _compile(fn, one_chip, *shapes)


def test_wkv6_compiles(one_chip):
    """rwkv6-1b6 time mixing: 32 heads of size 64, seq 2048."""
    arch = configs.get("rwkv6-1b6")
    B, T, H, N = 2, 2048, arch.n_rwkv_heads, arch.rwkv_head_size
    seq = ((B, H, T, N), F32)
    fwd = _pallas("wkv6")

    def loss(r, k, v, w, u):
        return fwd(r, k, v, w, u).sum()

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)), one_chip,
             seq, seq, seq, seq, ((H, N), F32))


def test_mamba_scan_compiles(one_chip):
    """jamba-1.5-large selective scan: d_inner 16384, state 16, seq
    2048."""
    arch = configs.get("jamba-1.5-large")
    B, S, di, N = 1, 2048, arch.d_inner, arch.ssm_state
    fwd = _pallas("mamba_scan")

    def loss(dt, Bm, Cm, x, A, Dk):
        return fwd(dt, Bm, Cm, x, A, Dk).sum()

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), one_chip,
             ((B, S, di), F32), ((B, S, N), F32), ((B, S, N), F32),
             ((B, S, di), F32), ((di, N), F32), ((di,), F32))


def test_moe_dispatch_combine_compiles(one_chip):
    """phi-3.5-MoE dispatch/combine at a shape the Pallas backend accepts:
    d_model 4096, 16 experts top-2, a 32-token chunk on each of 8 rows."""
    arch = configs.get("phi3.5-moe-42b-a6.6b")
    B, S, D, E, K = 8, 32, arch.d_model, arch.n_experts, arch.top_k
    F = arch.moe_d_ff
    C = capacity(S, arch)
    fn = _pallas("moe_dispatch_combine")
    _compile(lambda x, g, i, wi, wg, wo: fn(x, g, i, wi, wg, wo, capacity=C),
             one_chip, ((B, S, D), F32), ((B, S, K), F32),
             ((B, S, K), I32), ((E, D, F), F32), ((E, D, F), F32),
             ((E, F, D), F32))


def _olmo_2x2_step(topology):
    """The searched train plan of olmo-1b at all 16 layers, as the
    benchmark's 2x2 cell trains it (16 x 2048 a step in 4 microbatches,
    ZeRO-1 moments), and its step compiled for the described host."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import AxisSpec, ICI_BW, MeshSpec
    from repro.core.sharding import use_mesh
    from repro.models import lm
    from repro.optim import adamw_init
    from repro.plans import (adamw_shardings, build_parallel_plan,
                             param_pspecs, to_shardings)
    from repro.train import TrainConfig, make_train_step

    mesh = Mesh(np.asarray(topology.devices).reshape(2, 2), ("data", "model"))
    spec = MeshSpec(axes=(AxisSpec("data", 2, ICI_BW),
                          AxisSpec("model", 2, ICI_BW)))
    pplan = build_parallel_plan(OLMO, spec, phases=("train",),
                                train_seq=2048, train_batch=16)
    plan = pplan.plan_for("train")
    step = make_train_step(OLMO, plan, TrainConfig(
        q_chunk=256, loss_chunk=512, microbatches=4))
    params = jax.eval_shape(lambda k: lm.init_lm(k, OLMO, F32),
                            jax.random.PRNGKey(0))
    p_sh = to_shardings(param_pspecs(params, OLMO, plan), mesh, like=params)
    o_sh = adamw_shardings(p_sh, mesh)
    b_sh = {"tokens": NamedSharding(mesh, PartitionSpec())}
    batch = {"tokens": jax.ShapeDtypeStruct((16, 2048), I32)}
    with use_mesh(mesh):
        compiled = jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1),
        ).lower(params, jax.eval_shape(adamw_init, params), batch).compile()
    return pplan, compiled


@pytest.fixture(scope="module")
def olmo_xla_step(topology):
    """The step as the CPU host selects its kernels: chunked-XLA
    attention."""
    return _olmo_2x2_step(topology)


def _held(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes


def test_olmo_1b_train_step_fits_the_2x2_host(olmo_xla_step):
    """olmo-1b at all 16 layers as the benchmark's 2x2 cell trains it:
    16 x 2048 a step in 4 microbatches under the searched plan, with
    ZeRO-1 moments.  XLA's arguments and temporaries a chip fit the
    v5e's 15.75 GiB, and the plan search's count (persistent state plus
    its activation allowance) holds them with at most 10% to spare."""
    pplan, compiled = olmo_xla_step
    held = _held(compiled)
    counted = pplan.meta["phases"]["train"]["device_bytes"]
    assert held < 15.75 * 2**30, held
    assert held <= counted <= 1.1 * held, (held, counted)


def test_olmo_1b_train_step_maps_pallas_attention_on_the_2x2_host(
        topology, olmo_xla_step, monkeypatch):
    """With the platform a TPU, the same step runs the Pallas flash
    attention, forward and blocked backward, shard-mapped on each chip's
    slice: the plan splits attention by batch (layers 1-15) and by batch
    and heads (layer 0), which the kernel can take locally.  It still
    fits, gathers no more than the chunked-XLA step (so no q/k/v is
    gathered whole around the kernel), and the search's plan does not
    depend on the attention's backend."""
    from repro import compat
    xla_plan, xla_step = olmo_xla_step
    monkeypatch.setattr(compat, "default_platform", lambda: "tpu")
    dispatch.reset_selection_counts()
    pplan, compiled = _olmo_2x2_step(topology)
    assert str(pplan.plan_for("train")) == str(xla_plan.plan_for("train"))
    attn = [str(seg.plan[0]["attn"]).split("+")[0]
            for seg in pplan.plan_for("train").segments]
    assert attn == ["LayerConfig{batch:(data), heads:(model)}",
                    "LayerConfig{batch:(data,model)}"], attn
    assert dispatch.spmd_maps() == {("flash_attention", "pallas"): 2}
    assert dispatch.spmd_skips() == {}
    hlo = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert kernel in hlo, kernel
    assert _held(compiled) < 15.75 * 2**30, _held(compiled)

    def gathers(text):
        return len(re.findall(r"= \S+ all-gather(?:-start)?\(", text))
    assert gathers(hlo) <= gathers(xla_step.as_text()), (
        gathers(hlo), gathers(xla_step.as_text()))
