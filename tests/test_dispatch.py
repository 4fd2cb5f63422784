"""Kernel dispatch registry: backend selection, overrides, backend
agreement.

Selection is platform-sensitive; this suite asserts the CPU-host
behavior (Pallas TPU kernels cannot lower on CPU, so auto-selection must
resolve to the reference / interpret / XLA family, never native
"pallas").
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core.sharding import use_mesh
from repro.kernels import dispatch, ops, ref


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _flash_args(B=1, H=4, KH=2, S=128, D=64):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (_rand(ks[0], (B, H, S, D)), _rand(ks[1], (B, KH, S, D)),
            _rand(ks[2], (B, KH, S, D)))


def _decode_args(B=1, KH=2, G=4, T=256, D=64):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return (_rand(ks[0], (B, KH, G, D)), _rand(ks[1], (B, KH, T, D)),
            _rand(ks[2], (B, KH, T, D)), 100)


def _wkv_args(B=1, H=2, T=64, N=32):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r, k, v = (_rand(ks[i], (B, H, T, N)) * 0.5 for i in range(3))
    w = jnp.exp(-jnp.exp(_rand(ks[3], (B, H, T, N)) - 1.0))
    u = _rand(ks[4], (H, N)) * 0.5
    return r, k, v, w, u


# --------------------------------------------------------------------------- #
# (a) selection on CPU
# --------------------------------------------------------------------------- #
@pytest.mark.skipif(compat.default_platform() != "cpu",
                    reason="asserts CPU-host selection")
def test_cpu_auto_selection_avoids_native_pallas():
    q, k, v = _flash_args()
    assert dispatch.select("flash_attention", q, k, v,
                           causal=True).backend in ("ref", "interpret")
    dq, dk, dv, n = _decode_args()
    assert dispatch.select("decode_attention", dq, dk, dv,
                           n).backend in ("ref", "interpret")
    r, kk, vv, w, u = _wkv_args()
    assert dispatch.select("wkv6", r, kk, vv, w,
                           u).backend in ("ref", "interpret")


@pytest.mark.skipif(compat.default_platform() != "cpu",
                    reason="asserts CPU-host selection")
def test_cpu_large_shapes_fall_back_to_chunked_xla():
    # score tensor would be B*H*S*T = 2^26 elements: over the ref guard
    q, k, v = _flash_args(B=1, H=4, S=4096, D=8)
    assert dispatch.select("flash_attention", q, k, v,
                           causal=True).backend == "xla"
    # the guard is a preference, not a capability: forcing ref still works
    assert dispatch.select("flash_attention", q, k, v,
                           backend="ref").backend == "ref"


def _mamba_args(B=1, S=32, di=8, N=4):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    dt = jax.nn.softplus(_rand(ks[0], (B, S, di)))
    Bm, Cm = _rand(ks[1], (B, S, N)), _rand(ks[2], (B, S, N))
    x = _rand(ks[3], (B, S, di))
    A = -jnp.exp(_rand(ks[4], (di, N)) * 0.2)
    return dt, Bm, Cm, x, A, jnp.ones((di,), jnp.float32)


def _moe_args(B=1, S=32, D=8, E=4, K=2, F=16):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = _rand(ks[0], (B, S, D))
    wi, wg = _rand(ks[1], (E, D, F)), _rand(ks[2], (E, D, F))
    wo = _rand(ks[3], (E, F, D))
    gv, ei = jax.lax.top_k(jax.nn.softmax(_rand(ks[4], (B, S, E))), K)
    return x, gv, ei, wi, wg, wo


def test_xla_override_registered_for_every_op():
    """--kernel-backend xla must not crash any op (serve/train advertise
    it); for decode/wkv6 it aliases the linear-memory reference."""
    q, k, v = _flash_args()
    assert dispatch.select("flash_attention", q, k, v,
                           backend="xla").backend == "xla"
    dq, dk, dv, n = _decode_args()
    assert dispatch.select("decode_attention", dq, dk, dv, n,
                           backend="xla").backend == "xla"
    r, kk, vv, w, u = _wkv_args()
    assert dispatch.select("wkv6", r, kk, vv, w, u,
                           backend="xla").backend == "xla"
    assert dispatch.select("mamba_scan", *_mamba_args(),
                           backend="xla").backend == "xla"
    assert dispatch.select("moe_dispatch_combine", *_moe_args(),
                           capacity=16, backend="xla").backend == "xla"


def test_per_op_env_override_covers_new_ops(monkeypatch):
    """REPRO_KERNEL_BACKEND_<OP> steers one op without touching others."""
    monkeypatch.setenv(f"{dispatch.ENV_GLOBAL}_MAMBA_SCAN", "xla")
    assert dispatch.select("mamba_scan", *_mamba_args()).backend == "xla"
    q, k, v = _flash_args()
    assert dispatch.select("flash_attention", q, k, v).backend != "xla"
    monkeypatch.setenv(f"{dispatch.ENV_GLOBAL}_MOE_DISPATCH_COMBINE", "ref")
    assert dispatch.select("moe_dispatch_combine", *_moe_args(),
                           capacity=16).backend == "ref"


def test_unknown_op_and_backend_raise():
    q, k, v = _flash_args()
    with pytest.raises(KeyError):
        dispatch.call("no_such_op", q)
    with pytest.raises(ValueError):
        dispatch.call("flash_attention", q, k, v, backend="no_such_backend")


# --------------------------------------------------------------------------- #
# (b) overrides: env var and context
# --------------------------------------------------------------------------- #
def test_env_override(monkeypatch):
    q, k, v = _flash_args()
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "interpret")
    assert dispatch.select("flash_attention", q, k, v).backend == "interpret"
    # per-op override beats the global one
    monkeypatch.setenv(f"{dispatch.ENV_GLOBAL}_FLASH_ATTENTION", "xla")
    assert dispatch.select("flash_attention", q, k, v).backend == "xla"
    r, kk, vv, w, u = _wkv_args()
    assert dispatch.select("wkv6", r, kk, vv, w, u).backend == "interpret"


def test_env_override_through_public_ops(monkeypatch):
    q, k, v = _flash_args()
    want = np.asarray(ref.attention_ref(q, k, v, causal=True), np.float32)
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "interpret")
    got = np.asarray(ops.flash_attention(q, k, v, causal=True), np.float32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_force_backend_context():
    q, k, v = _flash_args()
    with dispatch.force_backend("xla"):
        assert dispatch.select("flash_attention", q, k, v).backend == "xla"
        with dispatch.force_backend(None):
            pass  # nesting restores cleanly
        assert dispatch.select("flash_attention", q, k, v).backend == "xla"
    # explicit backend= argument beats the forced context
    with dispatch.force_backend("xla"):
        assert dispatch.select("flash_attention", q, k, v,
                               backend="ref").backend == "ref"


def test_env_override_falls_back_when_call_unsupported(monkeypatch):
    """An env/context preference a backend cannot honor for a particular
    call (stateful wkv6 on the stateless interpret kernel) must fall back
    to auto-selection, not crash the model."""
    r, k, v, w, u = _wkv_args()
    s0 = jnp.zeros((1, 2, 32, 32), jnp.float32)
    monkeypatch.setenv(dispatch.ENV_GLOBAL, "interpret")
    impl = dispatch.select("wkv6", r, k, v, w, u, chunk=16,
                           initial_state=s0, return_state=True)
    assert impl.backend in ("ref", "xla")
    with dispatch.force_backend("interpret"):
        impl = dispatch.select("wkv6", r, k, v, w, u, chunk=16,
                               initial_state=s0, return_state=True)
        assert impl.backend in ("ref", "xla")
    # ... but an explicit backend= argument stays strict
    with pytest.raises(ValueError):
        dispatch.select("wkv6", r, k, v, w, u, chunk=16, initial_state=s0,
                        return_state=True, backend="interpret")


def test_forced_ineligible_backend_raises():
    if compat.default_platform() == "tpu":
        pytest.skip("pallas is eligible on TPU")
    q, k, v = _flash_args()
    with pytest.raises(ValueError):
        dispatch.call("flash_attention", q, k, v, backend="pallas")


# --------------------------------------------------------------------------- #
# (c) backend agreement on small shapes
# --------------------------------------------------------------------------- #
TOL = 2e-5


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backends_agree(causal):
    q, k, v = _flash_args()
    outs = {b: np.asarray(ops.flash_attention(q, k, v, causal=causal,
                                              backend=b), np.float32)
            for b in ("ref", "interpret", "xla")}
    for b in ("interpret", "xla"):
        np.testing.assert_allclose(outs[b], outs["ref"], atol=TOL, rtol=TOL,
                                   err_msg=f"backend {b} vs ref")


def test_decode_backends_agree():
    q, k, v, kv_len = _decode_args()
    a = ops.decode_attention(q, k, v, kv_len, backend="ref")
    b = ops.decode_attention(q, k, v, kv_len, backend="interpret")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=TOL, rtol=TOL)


def test_wkv6_backends_agree_and_state_matches_oracle():
    r, k, v, w, u = _wkv_args()
    a = ops.wkv6(r, k, v, w, u, chunk=16, backend="ref")
    b = ops.wkv6(r, k, v, w, u, chunk=16, backend="interpret")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=5 * TOL, rtol=5 * TOL)
    # stateful form against the (B, T, H, N)-layout oracle
    out, state = ops.wkv6(r, k, v, w, u, chunk=16, return_state=True)
    tm = lambda x: x.transpose(0, 2, 1, 3)
    want_out, want_state = ref.wkv6_ref(tm(r), tm(k), tm(v), tm(w), u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(tm(want_out), np.float32),
                               atol=5 * TOL, rtol=5 * TOL)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=5 * TOL, rtol=5 * TOL)


def test_wkv6_carried_state_splits_sequence():
    """Running [0:T/2] then [T/2:T] with the carried state must equal one
    full-length pass (the serve path contract)."""
    r, k, v, w, u = _wkv_args(T=64)
    half = 32
    full, s_full = ops.wkv6(r, k, v, w, u, chunk=16, return_state=True)
    cut = lambda x, a, b: x[:, :, a:b]
    o1, s1 = ops.wkv6(*(cut(x, 0, half) for x in (r, k, v, w)), u,
                      chunk=16, return_state=True)
    o2, s2 = ops.wkv6(*(cut(x, half, 64) for x in (r, k, v, w)), u,
                      chunk=16, initial_state=s1, return_state=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([o1, o2], axis=2)), np.asarray(full),
        atol=5 * TOL, rtol=5 * TOL)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=5 * TOL, rtol=5 * TOL)


# --------------------------------------------------------------------------- #
# differentiability: fwd-only kernels get a reference VJP
# --------------------------------------------------------------------------- #
def test_interpret_backend_is_differentiable():
    q, k, v = _flash_args(S=64)

    def loss(q):
        return ops.flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32, backend="interpret").sum()

    g_kernel = jax.grad(loss)(q)
    g_ref = jax.grad(
        lambda q: ref.attention_ref(q, k, v, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("S,path", [
    (256, "pallas"),       # lane-aligned: the Pallas backward
    (192, "reference"),    # the forward takes it; no 128-multiple block
])
def test_flash_backward_path_is_counted(S, path):
    """Each backward resolution of the kernel op is on record, and either
    path gives the reference's gradients."""
    q, k, v = _flash_args(S=S)

    def loss(q, k, v):
        return dispatch.call("flash_attention", q, k, v, causal=True,
                             block_q=64, block_k=64,
                             backend="interpret").sum()

    dispatch.reset_selection_counts()
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    counts = dispatch.selection_counts()
    assert counts.get(("flash_attention_bwd", path), 0) >= 1
    assert [p for (op, p) in counts if op == "flash_attention_bwd"] == [path]
    want = jax.grad(lambda q, k, v: ref.attention_ref(q, k, v,
                                                      causal=True).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)
    dispatch.reset_selection_counts()


# --------------------------------------------------------------------------- #
# block sizes
# --------------------------------------------------------------------------- #
def test_block_candidates():
    assert dispatch.block_candidates(256, (512, 256, 128)) == [256, 128]
    assert dispatch.block_candidates(100, (512, 256, 128)) == [100]


# --------------------------------------------------------------------------- #
# nothing hides the device: interpret is never automatic, every
# resolution is counted, an unknown TPU kind is refused
# --------------------------------------------------------------------------- #
@pytest.fixture
def fake_op():
    """An op whose only eligible impl is the interpreter."""
    dispatch.register("op_fake", "interpret", priority=20)(lambda x: x)
    dispatch.register("op_fake", "xla", priority=50,
                      supports=lambda x: False)(lambda x: x)
    yield "op_fake"
    dispatch._REGISTRY.pop("op_fake", None)


def test_interpret_is_never_an_automatic_choice(fake_op, monkeypatch):
    x = jnp.ones((4,))
    with pytest.raises(RuntimeError, match="no eligible backend"):
        dispatch.select(fake_op, x)
    assert dispatch.select(fake_op, x, backend="interpret").backend == \
        "interpret"
    # on a TPU host the real ops resolve to pallas or a production
    # backend, never to the interpreter
    monkeypatch.setattr(compat, "default_platform", lambda: "tpu")
    q, k, v = _flash_args(S=256, D=128)
    assert dispatch.select("flash_attention", q, k, v).backend == "pallas"
    q, k, v = _flash_args(S=100)         # unaligned: no native kernel
    assert dispatch.select("flash_attention", q, k, v).backend in (
        "ref", "xla")
    r, kk, vv, w, u = _wkv_args()
    s0 = jnp.zeros((1, 2, 32, 32), jnp.float32)
    assert dispatch.select("wkv6", r, kk, vv, w, u, initial_state=s0,
                           return_state=True).backend in ("ref", "xla")


def test_selection_counts_and_spmd_skips(monkeypatch):
    dispatch.reset_selection_counts()
    q, k, v = _flash_args()
    for _ in range(2):
        dispatch.call("flash_attention", q, k, v, backend="ref")
    dispatch.call("flash_attention", q, k, v, backend="xla")
    assert dispatch.selection_counts() == {("flash_attention", "ref"): 2,
                                           ("flash_attention", "xla"): 1}
    assert dispatch.spmd_skips() == {}
    # under a multi-device mesh the spmd-unsafe native kernel is passed
    # over where the call's specs cannot be mapped (a sequence split),
    # and the skip is on record
    monkeypatch.setattr(compat, "default_platform", lambda: "tpu")
    monkeypatch.setattr(dispatch, "_mesh_active", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    P = jax.sharding.PartitionSpec
    seq_split = (P("data", None, "model"),) * 3
    mappable = (P("data", "model"),) * 3
    q, k, v = _flash_args(S=256, D=128)
    with use_mesh(mesh):
        assert dispatch.select("flash_attention", q, k, v,
                               specs=seq_split).backend != "pallas"
        assert dispatch.spmd_skips() == {("flash_attention", "pallas"): 1}
        # where they can, it runs shard-mapped, counted in both tables
        assert dispatch.select("flash_attention", q, k, v,
                               specs=mappable).backend == "pallas"
        dispatch.reset_selection_counts()
        dispatch.call("flash_attention", q, k, v, specs=mappable,
                      backend="interpret")
    assert dispatch.spmd_maps() == {("flash_attention", "interpret"): 1}
    assert dispatch.selection_counts() == {
        ("flash_attention", "interpret"): 1}
    assert dispatch.spmd_skips() == {}
    dispatch.reset_selection_counts()
    assert (dispatch.selection_counts() == {} and dispatch.spmd_skips() == {}
            and dispatch.spmd_maps() == {})


def test_chip_spec_by_device_kind():
    from repro.core.device import TPU_V5E, chip_for
    assert chip_for("tpu", "TPU v5 lite") is TPU_V5E
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        chip_for("tpu", "TPU v99 imaginary")
    said = []
    assert chip_for("cpu", "cpu", log=said.append) is TPU_V5E
    assert said and "v5e" in said[0]
