"""FLOP counts against hand counts, the peak table, and the traffic
generator's promise that every seed offers the same work."""

import json

import numpy as np
import pytest

from bench.core import flops, peaks
from bench.core.spec import BENCH
from bench.traffic import open_loop


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, gflop", [
    # olmo-1b: N = 16 * (4 * 2048^2 + 3 * 2048 * 8192) + 2048 * 50304
    #          = 1,176,764,416; 6N = 7.0606e9; attention 3 * 4 * 2048 *
    #          (2049 / 2) * 16 = 4.03e8 -> 7.46 GFLOP/token
    ("olmo-1b", 7.4634),
    # granite-3-2b-d8: N = 8 * (2048 * 3072 + 2048^2 + 3 * 2048 * 8192)
    #          + 2048 * 49155 = 587,208,704; 6N = 3.5233e9; attention
    #          3 * 4 * 2048 * (2049 / 2) * 8 = 2.01e8 -> 3.72 GFLOP/token
    ("granite-3-2b-d8", 3.7246),
])
def test_train_flops_per_token_hand_count(name, gflop):
    got = flops.train_flops_per_token(config(name), 2048) / 1e9
    assert got == pytest.approx(gflop, rel=1e-3)


def test_matmul_params_hand_count():
    assert flops.matmul_params(config("olmo-1b")) == 1_176_764_416
    assert flops.matmul_params(config("granite-3-2b-d8")) == 587_208_704


def test_serve_flops_adds_up_to_a_forward():
    cfg = config("olmo-1b")
    one_pass = flops.serve_flops(cfg, 0, 64)
    by_token = sum(flops.serve_flops(cfg, p, 1) for p in range(64))
    assert one_pass == pytest.approx(by_token)


def test_flash_and_paged_decode_costs():
    fl, nb = flops.flash_attention_cost(1, 2, 1, 4, 8, causal=True,
                                        itemsize=4)
    assert fl == 4 * 2 * 8 * 10 and nb == 4 * 8 * (4 + 2) * 4
    cfg = config("granite-3-2b-d8")
    fl, nb = flops.paged_decode_cost(cfg, [100, 28], itemsize=4)
    assert nb == 8 * (2 * 8 * 64 * 128 + 2 * 32 * 64 * 2) * 4
    assert fl == 4 * 32 * 64 * 128 * 8


def test_peaks_known_and_unknown():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.least_time(p, 197e12, 1.0) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def chat():
    return json.loads((BENCH / "traffic" / "chat.json").read_text())


def test_every_seed_offers_the_same_work():
    a = open_loop.schedule(chat(), 1, 30, 50304)
    b = open_loop.schedule(chat(), 2**40 + 3, 30, 50304)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert (sorted(r.max_new_tokens for r in a)
            == sorted(r.max_new_tokens for r in b))
    assert sum(r.shared for r in a) == sum(r.shared for r in b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    again = open_loop.schedule(chat(), 1, 30, 50304)
    assert [(r.due, r.prompt) for r in a] == [(r.due, r.prompt) for r in again]


def test_every_window_holds_the_same_mix():
    """The seed reorders the work, but each window gets the same arrivals
    and nearly the same output tokens: a run's load does not hang on its
    seed."""
    import statistics

    p = chat()
    due_out, count = [], set()
    for seed in range(2**33, 2**33 + 24):
        win = [r for r in open_loop.schedule(p, seed, 51, 50304)
               if 0 <= r.due < 51]
        count.add(len(win))
        due_out.append(sum(r.max_new_tokens for r in win))
    assert count == {round(p["rate"] * 51)}
    q = statistics.quantiles(due_out, n=4)
    assert (q[2] - q[0]) / statistics.median(due_out) < 0.05


def test_kronecker_order_is_a_permutation():
    rng = np.random.default_rng(3)
    ranks = open_loop.kronecker_ranks(50, open_loop.ALPHA["gaps"], rng)
    assert sorted(ranks) == list(range(50))
    # any 10 consecutive items hold ranks from every fifth of the range
    for lo in range(0, 40):
        assert len({r // 10 for r in ranks[lo:lo + 10]}) == 5


def test_schedule_shape():
    p = chat()
    reqs = open_loop.schedule(p, 7, 30, 50304)
    due = np.asarray([r.due for r in reqs])
    assert due.min() == -p["ramp_s"] and due.max() < 30
    assert len(reqs) == round(p["rate"] * p["ramp_s"]) + round(p["rate"] * 30)
    assert (due == 0).sum() == 1    # the window's first arrival opens it
    lens = [len(r.prompt) for r in reqs]
    assert all(n % p["granule"] == 0 for n in lens)
    assert min(lens) >= p["prompt"]["min"] and max(lens) <= p["prompt"]["max"]
    assert not any(r.shared for r in reqs)    # the mix shares no prefix
    shared = open_loop.schedule(
        dict(p, shared_prefix={"tokens": 384, "share": 0.6}), 7, 30, 50304)
    system = next(r.prompt for r in shared if r.shared)[:384]
    assert sum(r.shared for r in shared) == round(0.6 * len(shared))
    assert all(r.prompt[:384] == system for r in shared if r.shared)
    assert max(len(r.prompt) + r.max_new_tokens for r in reqs) <= \
        open_loop.max_len(p)


def test_leaf_gap_against_the_larger_of_leaf_and_median():
    from bench.core import checks

    ref = {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-6}
    # "tiny" moves by round-off alone: its gradient is under 1e-3 of the
    # median leaf's, so it is left out however far it reads
    skip = checks.negligible_leaves(ref)
    assert skip == ["tiny"]
    prog = {"a": 1.1, "b": 2.0, "c": 4.8, "tiny": 5.0}
    gap, leaf = checks.leaf_gap(prog, ref, skip)
    # a: 0.1 / max(1, median 1.5); c: 0.8 / 4
    assert leaf == "c" and gap == pytest.approx(0.2)
    ok, compared = checks.judge({"x": gap}, {"x": 0.25})
    assert ok and compared == {"x": {"value": gap, "limit": 0.25}}
    assert not checks.judge({"x": gap}, {})[0]
