"""Persistent block-size autotune cache: disk round-trip, corrupt-file
recovery, env-dir override, and the zero-re-tune restart contract."""

import json

import pytest

from repro.kernels import dispatch


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets a fresh tmp cache dir and clean counters."""
    monkeypatch.setenv(dispatch.ENV_CACHE_DIR, str(tmp_path))
    dispatch.clear_autotune_cache()
    yield tmp_path
    dispatch.clear_autotune_cache()


def _tune(op="op_cache", key=("k",), cands=((128, 128), (64, 64))):
    return dispatch.tuned_blocks(op, key, list(cands),
                                 bench=lambda *a: None, args=())


def test_round_trip_to_disk(tmp_path):
    got = _tune()
    assert got == (128, 128)
    path = dispatch.autotune_cache_path()
    assert path.parent == tmp_path
    data = json.loads(path.read_text())
    assert list(data.values()) == [[128, 128]]
    stats = dispatch.autotune_cache_stats()
    assert stats.get("tuned") == 1 and stats.get("disk_writes") == 1

    # a fresh process (simulated: clear the in-process layer) re-tunes
    # nothing — the disk entry serves.
    dispatch.clear_autotune_cache()
    assert _tune() == (128, 128)
    stats = dispatch.autotune_cache_stats()
    assert stats.get("disk_hits") == 1
    assert stats.get("tuned", 0) == 0

    # and subsequent same-process calls hit the in-memory layer
    assert _tune() == (128, 128)
    assert dispatch.autotune_cache_stats().get("memory_hits") == 1


def test_zero_retunes_after_restart_many_entries():
    """The serve-restart contract: after persistence, a second in-process
    run performs zero re-tunes (cache hit counters prove it)."""
    n = 5
    for i in range(n):
        _tune(key=(f"shape{i}",), cands=((256,), (128,)))
    assert dispatch.autotune_cache_stats().get("tuned") == n

    dispatch.clear_autotune_cache()          # "restart"
    for i in range(n):
        _tune(key=(f"shape{i}",), cands=((256,), (128,)))
    stats = dispatch.autotune_cache_stats()
    assert stats.get("tuned", 0) == 0
    assert stats.get("disk_hits") == n


def test_corrupt_file_recovers_by_retuning():
    path = dispatch.autotune_cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("{not json!!")
    got = _tune()
    assert got == (128, 128)                 # fell back to a fresh tune
    stats = dispatch.autotune_cache_stats()
    assert stats.get("disk_errors") == 1 and stats.get("tuned") == 1
    # the re-tune rewrote the file into a loadable state
    dispatch.clear_autotune_cache()
    assert _tune() == (128, 128)
    assert dispatch.autotune_cache_stats().get("disk_hits") == 1


def test_stale_disk_entry_is_ignored():
    """A disk choice no longer in the candidate list must not be served."""
    _tune(cands=((64, 64), (32, 32)))
    dispatch.clear_autotune_cache()
    got = _tune(cands=((128, 128), (256, 256)))   # candidate set changed
    assert got == (128, 128)
    assert dispatch.autotune_cache_stats().get("tuned") == 1


def test_cache_dir_override_respected(tmp_path, monkeypatch):
    other = tmp_path / "elsewhere"
    monkeypatch.setenv(dispatch.ENV_CACHE_DIR, str(other))
    dispatch.clear_autotune_cache()
    _tune()
    assert dispatch.autotune_cache_dir() == other
    assert (other / dispatch.autotune_cache_path().name).exists()


def test_persistence_can_be_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv(dispatch.ENV_PERSIST, "0")
    _tune()
    assert not any(tmp_path.iterdir())


def test_candidate_that_fails_to_compile_is_counted_and_logged(
        monkeypatch, caplog):
    """A timed candidate that raises is skipped, logged at warning and
    counted — never dropped in silence."""
    from repro import compat
    monkeypatch.setattr(compat, "default_platform", lambda: "tpu")

    def bench(b):
        if b == 256:
            raise RuntimeError("Mosaic refused the block")

    with caplog.at_level("WARNING", logger=dispatch.__name__):
        got = dispatch.tuned_blocks("op_fail", ("k",), [(256,), (128,)],
                                    bench=bench, args=())
    assert got == (128,)
    assert dispatch.autotune_cache_stats().get("compile_failures") == 1
    assert any("failed" in r.getMessage() for r in caplog.records)


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
