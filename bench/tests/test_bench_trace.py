"""Trace reduction against hand-made timelines."""

import pytest

from bench.core import trace as T


def ops(*spec):
    return [(name, s, e, detail) for name, s, e, detail in spec]


def test_union_busy_and_idle_share():
    dev = ops(("a", 0, 10, ""), ("b", 5, 20, ""), ("c", 40, 50, ""))
    assert T.union([(0, 10), (5, 20), (40, 50)]) == [(0, 20), (40, 50)]
    assert T.busy_ns(dev, 0, 100) == 30
    assert T.busy_ns(dev, 10, 45) == 15
    tr = T.Trace(device_ops=[dev, dev[:1]],
                 host_spans=[("engine.step", 0, 60), ("engine.submit", 60, 100)])
    # chip 0 busy 30 of 100, chip 1 busy 10 of 100: mean busy 20
    assert T.idle_share(tr) == pytest.approx(80.0)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    dev = ops(("a", 0, 10, ""), ("b", 30, 40, ""))
    spans = [("train.step", 0, 100), ("train.fetch", 45, 100)]
    gaps = dict(T.idle_gaps(dev, spans, 0, 100))
    assert gaps == {"train.step": 20e-9, "train.fetch": 60e-9}


def test_op_time_matches_name_or_detail():
    dev = ops(("custom-call.3", 0, 7, "jit(step)/_paged_kernel"),
              ("fusion.1", 7, 9, "jit(step)/dot_general"),
              ("custom-call.4", 9, 12, "jit(step)/_paged_kernel"))
    assert T.op_ns(dev, r"_paged_kernel") == (10, 2)
    assert T.op_ns(dev, r"^fusion") == (2, 1)
    assert T.top_ops(dev, 1) == [["custom-call.3", 7e-9]]


def test_exposed_collective_time():
    dev = ops(("all-reduce.1", 0, 10, ""), ("fusion.2", 5, 8, ""),
              ("all-gather.3", 20, 30, ""))
    assert T.exposed_collective_ns(dev, 0, 100) == 7 + 10


def test_recorded_chip_trace():
    """150 ms of a traced olmo-1b.chat run on one v5e (XLA Ops of TPU:0
    and the harness spans), cut from the recorded profile with HLO names
    shortened: the reduction reads what it read on the chip."""
    import json
    from pathlib import Path

    data = json.loads((Path(__file__).parent / "data" /
                       "chat_trace_excerpt.json").read_text())
    ops = [tuple(o) for o in data["ops"]]
    spans = [tuple(s) for s in data["spans"]]
    tr = T.Trace(device_ops=[ops], host_spans=spans)
    want = data["expect"]
    assert T.idle_share(tr) == pytest.approx(want["idle_share"])
    assert 0 < T.idle_share(tr) < 100
    ns, calls = T.op_ns(ops, T.pallas_call((16, 16, 1, 128)))
    assert [ns, calls] == want["kernel"]
    # one paged-decode call per layer (16) per engine step
    assert calls % 16 == 0 and calls > 0
    steps = [s for s in spans if s[0] == "engine.step"]
    for _, s, e, _ in T.matching(ops, T.pallas_call((16, 16, 1, 128))):
        assert any(a <= s and e <= b for _, a, b in steps)
    assert T.idle_gaps(ops, spans, *tr.window) == [
        [n, pytest.approx(v)] for n, v in want["idle_gaps"]]


def test_load_reads_host_spans_of_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("train.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(tmp_path, ("train.step",))
    assert [s[0] for s in tr.host_spans] == ["train.step"]
    lo, hi = tr.window
    assert hi > lo


def test_paged_decode_roofline_counts_only_width_one_steps():
    """A step that carries a prompt chunk attends through the mixed
    step's XLA gather, not the Pallas kernel: its decode work must not
    enter the numerator, whose denominator holds only the kernel's
    calls."""
    import types

    from bench.core import flops
    from bench.core.peaks import PEAKS, least_time
    from bench.run import load_reader
    from conftest import toy_config

    cfg = toy_config("olmo-1b")
    H, KH, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    kernel = (f"%custom-call.1 = f32[4,{KH},{H // KH},{D}]{{3,2,1,0}} "
              f"custom-call(%a), custom_call_target=\"tpu_custom_call\"")
    dev = ops((kernel, 100, 400, ""), ("fusion.2", 400, 900, ""),
              (kernel, 1000, 1300, ""))
    decode = [{"ts": 0.0, "te": 1.0, "width": 1, "decode_kv_lens": [40, 90]},
              {"ts": 1.0, "te": 2.0, "width": 1, "decode_kv_lens": [41, 91]}]
    mixed = {"ts": 2.0, "te": 3.0, "width": 32,
             "decode_kv_lens": [42, 92, 300]}
    cell = types.SimpleNamespace(config=cfg, traffic={"slots": 4}, chips=1)
    read = load_reader("paged_decode_roofline.serve")
    peaks = PEAKS["TPU v5 lite"]

    def rec(steps):
        return {"trace": T.Trace(device_ops=[dev], host_spans=[]),
                "trace_window": (0.0, 10.0), "cell": cell, "peaks": peaks,
                "steps": steps}

    least = sum(least_time(peaks, *flops.paged_decode_cost(
        cfg, s["decode_kv_lens"], 4)) for s in decode)
    want = 100.0 * least / 600e-9
    assert read(rec(decode)) == pytest.approx(want)
    assert read(rec(decode + [mixed])) == pytest.approx(want)
    assert read(rec([mixed])) is None
