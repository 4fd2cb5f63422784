"""Training substrate: optimizer, data determinism, microbatching,
checkpoint fault tolerance (kill + resume bit-identical)."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM, make_dataset
from repro.models import lm, uniform_plan
from repro.models.arch import ShapeSpec
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.train import TrainConfig, make_train_step


def _setup(arch_name="llama3_2_1b", B=4, S=32):
    arch = C.reduced(arch_name)
    params = lm.init_lm(jax.random.PRNGKey(0), arch, jnp.float32)
    opt = adamw_init(params)
    shape = ShapeSpec("t", S, B, "train")
    ds = make_dataset(arch, shape)
    return arch, params, opt, ds


def test_data_pipeline_deterministic_and_resumable():
    ds = SyntheticLM(vocab=101, batch=8, seq_len=32, seed=3)
    a = ds.batch_at(7)["tokens"]
    b = ds.batch_at(7)["tokens"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, ds.batch_at(8)["tokens"])
    # host sharding partitions the global batch
    h0 = ds.batch_at(7, host_index=0, host_count=2)["tokens"]
    assert h0.shape == (4, 32)


def test_data_has_learnable_structure():
    ds = SyntheticLM(vocab=64, batch=4, seq_len=128, seed=0, noise=0.1)
    x = ds.batch_at(0)["tokens"]
    pred = (31 * x[:, :-1] + 17) % 64
    agree = np.mean(pred == x[:, 1:])
    assert agree > 0.8


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(cosine_schedule(cfg, jnp.int32(s))) for s in
           (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0, abs=0.01)
    assert 0.1 < lrs[3] < 1.0
    assert lrs[4] == pytest.approx(0.1, abs=0.01)


def test_grad_clip_engages():
    arch, params, opt, ds = _setup()
    batch = jax.tree.map(jnp.asarray, ds.batch_at(0))
    g = jax.grad(lambda p: lm.loss_fn(p, batch, arch)[0])(params)
    big = jax.tree.map(lambda x: x * 1e6, g)
    _, _, m = adamw_update(params, big, opt, AdamWConfig(clip_norm=1.0))
    assert float(m["grad_norm"]) > 1.0  # reported pre-clip


def test_microbatching_matches_full_batch():
    arch, params, opt, ds = _setup(B=4)
    batch = jax.tree.map(jnp.asarray, ds.batch_at(0))
    step1 = make_train_step(arch, None, TrainConfig(microbatches=1))
    step2 = make_train_step(arch, None, TrainConfig(microbatches=2))
    p1, _, m1 = jax.jit(step1)(params, opt, batch)
    p2, _, m2 = jax.jit(step2)(params, opt, batch)
    # same gradient direction: params nearly identical after one step
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_train_loss_decreases():
    """A few dozen steps on the learnable stream must reduce nll."""
    arch, params, opt, ds = _setup(B=8, S=64)
    cfg = TrainConfig(optimizer=AdamWConfig(lr=3e-3, warmup_steps=5,
                                            total_steps=60))
    step = jax.jit(make_train_step(arch, None, cfg))
    first = last = None
    for s in range(40):
        batch = jax.tree.map(jnp.asarray, ds.batch_at(s))
        params, opt, m = step(params, opt, batch)
        if s == 0:
            first = float(m["nll"])
        last = float(m["nll"])
    assert last < first - 0.3, (first, last)


def test_train_driver_run_overfits_one_batch(tmp_path, monkeypatch):
    """``launch.train.run`` as the chip smoke calls it: every step is in
    the history, one repeated batch drives the nll down, and a run with
    checkpointing off writes nothing."""
    from repro.launch import train

    monkeypatch.chdir(tmp_path)
    # the persistent cache would stay on for the rest of this worker
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    res = train.run(["--arch", "olmo-1b", "--width", "64", "--depth", "2",
                     "--vocab", "128", "--seq", "32", "--batch", "2",
                     "--steps", "8", "--ckpt-every", "0",
                     "--overfit-batches", "1"])
    nll = [h["nll"] for h in res["history"]]
    assert [h["step"] for h in res["history"]] == list(range(8))
    assert nll[-1] < nll[0] - 0.5, nll
    assert res["mesh"] == {"data": 1, "model": 1}
    assert all(w["devices"] == 1 for w in res["weight_shards"])
    assert not any(tmp_path.iterdir())


# --------------------------------------------------------------------------- #
# checkpoint fault tolerance
# --------------------------------------------------------------------------- #
def test_checkpoint_roundtrip(tmp_path):
    arch, params, opt, ds = _setup()
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(3, {"params": params, "opt": opt})
    step, state = mgr.restore_latest({"params": params, "opt": opt})
    assert step == 3
    for a, b in zip(jax.tree.leaves(params),
                    jax.tree.leaves(state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_and_corruption_fallback(tmp_path):
    arch, params, opt, ds = _setup()
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3):
        mgr.save(s, {"params": params})
    assert mgr.all_steps() == [2, 3]
    # corrupt the newest: restore falls back to the previous one
    (tmp_path / "step_00000003" / "arrays.npz").write_bytes(b"garbage")
    step, state = mgr.restore_latest({"params": params})
    assert step == 2 and state is not None
    # interrupted write (tmp dir) is ignored by step listing
    (tmp_path / "step_00000009.tmp").mkdir()
    assert 9 not in mgr.all_steps()
    # a step dir without a manifest (crash before rename) is ignored
    (tmp_path / "step_00000011").mkdir()
    assert mgr.latest_step() == 3


def test_kill_and_resume_bit_identical(tmp_path):
    """Fault-tolerance: train 6 steps straight vs train 3 + 'crash' +
    restore + 3 more — identical final params and losses."""
    arch, params0, opt0, ds = _setup(B=4, S=32)
    cfg = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=2,
                                            total_steps=10))
    step = jax.jit(make_train_step(arch, None, cfg))

    # run A: 6 uninterrupted steps
    p, o = params0, opt0
    for s in range(6):
        p, o, mA = step(p, o, jax.tree.map(jnp.asarray, ds.batch_at(s)))

    # run B: 3 steps, checkpoint, simulate crash, restore, 3 more
    mgr = CheckpointManager(tmp_path)
    pb, ob = params0, opt0
    for s in range(3):
        pb, ob, _ = step(pb, ob, jax.tree.map(jnp.asarray, ds.batch_at(s)))
    mgr.save(3, {"params": pb, "opt": ob})
    del pb, ob                                     # crash
    restored_step, state = mgr.restore_latest(
        {"params": params0, "opt": opt0})
    assert restored_step == 3
    pb, ob = state["params"], state["opt"]
    for s in range(3, 6):
        pb, ob, mB = step(pb, ob, jax.tree.map(jnp.asarray, ds.batch_at(s)))

    assert float(mA["loss"]) == pytest.approx(float(mB["loss"]), rel=1e-6)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_elastic_restore_dtype_and_shape(tmp_path):
    """Restore targets a different dtype 'like' tree (elastic re-sharding /
    re-casting on load)."""
    arch, params, opt, _ = _setup()
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"params": params})
    like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    step, state = mgr.restore_latest({"params": like}, verify=False)
    assert step == 1
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(state["params"]))


def test_compile_cache_dir_env_or_checkout(monkeypatch, tmp_path):
    """The persistent compilation cache honours JAX_COMPILATION_CACHE_DIR
    (JAX reads it; the helper sets nothing) and otherwise sits at a fixed
    ``.jax_cache`` in the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert updates == []
    monkeypatch.delenv(compile_cache.ENV)
    got = compile_cache.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    assert got == root / ".jax_cache"
    assert updates == [("jax_compilation_cache_dir", str(got))]
