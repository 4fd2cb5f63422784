"""The four-chip training cell and its collective reader, against
hand-made timelines."""

import pytest

from bench import run as bench_run
from bench.core import trace as T
from bench.core.spec import load_cell

CELL = "olmo-1b.train-2x2"
READER = "exposed_collective_share.train"


def rec(*chips, spans=(("train.step", 0, 100),)):
    return {"trace": T.Trace(device_ops=[list(c) for c in chips],
                             host_spans=list(spans))}


def test_exposed_collectives_over_busy_time_averaged_over_chips():
    read = bench_run.load_reader(READER)
    chip0 = [  # busy 50: all-reduce 0-10 half under a fusion, all-gather alone
        ("%all-reduce.1 = f32[8] all-reduce(f32[8] %p)", 0, 10, ""),
        ("%fusion.2 = f32[8] fusion(f32[8] %all-reduce.1)", 5, 10, ""),
        ("%all-gather.3 = f32[16] all-gather(f32[8] %x)", 20, 30, ""),
        ("%fusion.4 = f32[8] fusion(f32[8] %y)", 30, 60, ""),
    ]
    chip1 = [  # busy 40: its collective half under compute
        ("%convolution.6 = f32[8] convolution(f32[8] %a, f32[8] %b)", 0, 15, ""),
        ("%reduce-scatter.5 = f32[4] reduce-scatter(f32[8] %g)", 10, 20, ""),
        ("%fusion.7 = f32[8] fusion(f32[4] %reduce-scatter.5)", 20, 40, ""),
    ]
    # chip 0: 5 + 10 exposed of 50; chip 1: 5 of 40
    assert read(rec(chip0, chip1)) == pytest.approx(100 * (15 / 50 + 5 / 40) / 2)


def test_an_op_around_a_collective_is_a_loop_and_hides_nothing():
    read = bench_run.load_reader(READER)
    chip = [("%call.1 = f32[8] call(f32[8] %a), to_apply=%c", 0, 40, ""),
            ("%all-gather.2 = f32[8] all-gather(f32[4] %b)", 10, 20, "")]
    assert read(rec(chip)) == pytest.approx(100 * 10 / 40)


def test_a_loop_around_the_collectives_does_not_hide_them():
    """A ``while`` (or call) event spans every op it runs: it is not
    compute that overlaps them."""
    read = bench_run.load_reader(READER)
    chip = [
        ("%while.7 = (f32[8]) while((f32[8]) %t), body=%body", 0, 50, ""),
        ("%fusion.8 = f32[8] fusion(f32[8] %a)", 0, 20, ""),
        ("%all-reduce-start.9 = f32[8] all-reduce-start(f32[8] %b)", 20, 30, ""),
        ("%all-reduce-done.9 = f32[8] all-reduce-done(f32[8] %c)", 30, 50, ""),
    ]
    assert read(rec(chip)) == pytest.approx(100 * 30 / 50)


def test_collective_operands_do_not_make_an_op_a_collective():
    read = bench_run.load_reader(READER)
    chip = [("%fusion.1 = f32[8] fusion(f32[8] %all-gather.2)", 0, 10, "")]
    assert read(rec(chip)) == 0.0


def test_silent_without_a_trace():
    read = bench_run.load_reader(READER)
    assert read({"trace": None}) is None
    assert read(rec()) is None


def test_the_cell_takes_four_chips_and_its_per_layer_metrics():
    cell = load_cell(CELL)
    assert cell.chips == 4
    assert cell.config["num_hidden_layers"] == 16
    assert cell.traffic["batch"] == 16 and cell.traffic["microbatches"] == 4
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "mfu.train", "device_idle_share.train", "cost_model_error.train",
        "attention_backward_share.train", "stack_overhead_share.train",
        READER}


def test_the_sweep_reads_as_the_harness_function():
    """The reader's one-sweep exposed time equals
    ``trace.exposed_collective_ns`` on a random timeline (concurrent ops
    and gaps), and a 4-chip trace of 30,000 ops a chip reads in seconds."""
    import random
    import time

    from bench.run import load_reader

    mod = load_reader(READER).__globals__
    rng = random.Random(5)
    ops, t = [], 0
    for i in range(3000):
        d = rng.randint(10, 500)
        name = f"all-reduce.{i}" if rng.random() < 0.1 else f"fusion.{i}"
        start = t - rng.randint(0, 200) if rng.random() < 0.3 else t
        ops.append((name, max(0, start), max(0, start) + d, ""))
        t = start + d + rng.randint(0, 50)
    assert mod["exposed_ns"](ops, 100, t - 100) == T.exposed_collective_ns(
        ops, 100, t - 100)

    big = [(f"all-gather.{i}" if i % 20 == 0 else f"fusion.{i}",
            10 * i, 10 * i + 9, "") for i in range(30000)]
    t0 = time.perf_counter()
    assert bench_run.load_reader(READER)(rec(big, big, big, big, spans=(
        ("train.step", 0, 300000),))) == pytest.approx(100 * 1 / 20)
    assert time.perf_counter() - t0 < 10
