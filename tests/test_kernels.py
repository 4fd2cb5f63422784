"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Sweeps shapes and dtypes per kernel; asserts allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,H,KH,S,D", [
    (1, 4, 4, 256, 64),      # MHA
    (2, 4, 2, 256, 64),      # GQA 2:1
    (1, 8, 2, 512, 128),     # GQA 4:1, bigger head
    (1, 2, 1, 1024, 64),     # long seq, MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, H, KH, S, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, H, S, D), dtype)
    k = _rand(ks[1], (B, KH, S, D), dtype)
    v = _rand(ks[2], (B, KH, S, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("B,H,KH,S,D,block_q,block_k", [
    (1, 4, 4, 256, 64, 128, 128),     # MHA
    (2, 8, 2, 256, 64, 128, 256),     # GQA 4:1, block_q != block_k
    (1, 4, 1, 512, 64, 256, 128),     # MQA
    (1, 2, 2, 256, 128, 256, 256),    # one block each way, bigger head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_backward(B, H, KH, S, D, block_q, block_k, dtype,
                                  causal):
    """dq, dk, dv of the Pallas backward against the reference's VJP."""
    from repro.kernels.flash_attention import flash_attention_bwd
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = _rand(ks[0], (B, H, S, D), dtype)
    k = _rand(ks[1], (B, KH, S, D), dtype)
    v = _rand(ks[2], (B, KH, S, D), dtype)
    do = _rand(ks[3], (B, H, S, D), dtype)
    o, vjp = jax.vjp(lambda q, k, v: ref.attention_ref(q, k, v,
                                                       causal=causal),
                     q, k, v)
    got = flash_attention_bwd(q, k, v, o, do, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=True)
    for g, want in zip(got, vjp(do)):
        assert g.dtype == want.dtype and g.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(want, np.float32),
            atol=5 * TOL[dtype], rtol=5 * TOL[dtype])


@pytest.mark.parametrize("B,KH,G,T,D", [
    (1, 2, 4, 512, 64),
    (2, 4, 8, 1024, 128),
    (1, 1, 8, 2048, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("frac", [1.0, 0.37])
def test_decode_attention(B, KH, G, T, D, dtype, frac):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, KH, G, D), dtype)
    k = _rand(ks[1], (B, KH, T, D), dtype)
    v = _rand(ks[2], (B, KH, T, D), dtype)
    kv_len = max(1, int(T * frac))
    out = ops.decode_attention(q, k, v, kv_len, block_k=256, interpret=True)
    want = ref.decode_attention_ref(
        q.reshape(B, KH * G, D), k, v, kv_len).reshape(B, KH, G, D)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_attention_per_slot_kv_len():
    """kv_len as a (B,) vector (continuous batching: each cache slot at
    its own depth) masks each row independently — row b must equal a
    batch-1 call with scalar kv_len[b], on both ref and interpret."""
    B, KH, G, T, D = 3, 2, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, KH, G, D), jnp.float32)
    k = _rand(ks[1], (B, KH, T, D), jnp.float32)
    v = _rand(ks[2], (B, KH, T, D), jnp.float32)
    lens = jnp.asarray([3, 256, 117], jnp.int32)
    for backend in ("ref", "interpret"):
        out = ops.decode_attention(q, k, v, lens, backend=backend)
        for b in range(B):
            want = ops.decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                        int(lens[b]), backend=backend)
            np.testing.assert_allclose(
                np.asarray(out[b], np.float32),
                np.asarray(want[0], np.float32), atol=2e-5, rtol=2e-5)


def test_decode_attention_rejects_malformed_kv_len():
    B, KH, G, T, D = 2, 1, 4, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (B, KH, G, D), jnp.float32)
    k = _rand(ks[1], (B, KH, T, D), jnp.float32)
    v = _rand(ks[2], (B, KH, T, D), jnp.float32)
    for bad in (jnp.zeros((B + 1,), jnp.int32), jnp.zeros((B, 1), jnp.int32)):
        with pytest.raises(ValueError, match="kv_len"):
            ops.decode_attention(q, k, v, bad, backend="ref")


@pytest.mark.parametrize("B,H,T,N", [
    (1, 2, 128, 64),
    (2, 4, 256, 64),
    (1, 1, 64, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6(B, H, T, N, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    r = _rand(ks[0], (B, H, T, N), dtype) * 0.5
    k = _rand(ks[1], (B, H, T, N), dtype) * 0.5
    v = _rand(ks[2], (B, H, T, N), dtype) * 0.5
    # data-dependent decay in (0, 1), realistic RWKV6 range
    w = jnp.exp(-jnp.exp(_rand(ks[3], (B, H, T, N), jnp.float32) - 1.0))
    w = w.astype(dtype)
    u = _rand(ks[4], (H, N), dtype) * 0.5
    out = ops.wkv6(r, k, v, w, u, chunk=32, interpret=True)
    want, _ = ref.wkv6_ref(
        r.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), w.transpose(0, 2, 1, 3), u)
    want = want.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=5 * TOL[dtype], rtol=5 * TOL[dtype])


def test_flash_matches_model_core():
    """The Pallas kernel and the model's XLA attention agree."""
    from repro.models.layers import _mha_core
    B, S, KH, G, D = 1, 256, 2, 2, 64
    H = KH * G
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KH, D))
    v = jax.random.normal(ks[2], (B, S, KH, D))
    pos = jnp.arange(S)
    xla = _mha_core(q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2),
                    causal=True, q_positions=pos, kv_positions=pos,
                    q_chunk=64, kv_chunk=128)
    pal = ops.flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True,
                              block_q=64, block_k=64, interpret=True)
    pal = pal.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pal),
                               atol=2e-5, rtol=2e-5)
