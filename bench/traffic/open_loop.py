"""Open-loop serving traffic: requests due on a schedule, whatever the
server does (independent users).  The general generator every serving
mix names; a mix is its parameters in ``bench/traffic/<mix>.json``.

Every seed gets the same work in another order, and every window of the
schedule holds nearly the same mix.  The ramp and the measured window
each get ``round(rate * length)`` arrivals, whose gaps are the quantiles
of an exponential (a Poisson process's gaps, without its sampling noise)
scaled to fill that length; prompt and output lengths are the quantiles
of clipped lognormals.  Each of these lists is laid out in a
low-discrepancy order: item ``i`` takes the value whose rank is that of
``(phase + i * alpha) mod 1``, a Kronecker sequence with its own
irrational ``alpha`` and a ``phase`` the seed draws.  So any run of
consecutive requests samples the whole distribution evenly, on every
seed.  The seed also picks which requests open with the shared system
prompt (the same number on every seed, spread the same way) and draws the
token ids.

Parameters (``params``):

* ``rate`` — requests per second; ``ramp_s`` — seconds of traffic
  before the measured window, so the window opens on a filled server;
* ``prompt`` / ``output`` — ``{"median", "sigma", "min", "max"}`` of
  a lognormal, clipped; ``granule`` — prompt lengths are rounded up to a
  multiple of it (so the set of prefill chunk widths, each a compiled
  program, stays small);
* ``shared_prefix`` — ``{"tokens", "share"}``: that share of requests
  (chosen among prompts longer than the prefix) opens with one common
  prefix of that many tokens, the system prompt.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Scheduled:
    uid: int
    due: float                 # seconds after the window opens (< 0: ramp)
    prompt: tuple[int, ...]
    max_new_tokens: int
    shared: bool


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_lengths(spec: dict, n: int, granule: int = 1) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    return (np.ceil(x / granule) * granule).astype(np.int64)


# one irrational step per list, so the orders of gaps, prompts and outputs
# are not shifted copies of one another
ALPHA = {"gaps": (5 ** 0.5 - 1) / 2, "prompt": 2 ** 0.5 - 1,
         "output": 3 ** 0.5 - 1, "shared": 7 ** 0.5 - 2}


def kronecker_ranks(n: int, alpha: float, rng: np.random.Generator) -> np.ndarray:
    """The rank, among ``n``, of ``(phase + i * alpha) mod 1`` for each
    ``i``, with ``phase`` drawn from ``rng``."""
    keys = (rng.random() + np.arange(n) * alpha) % 1.0
    return np.argsort(np.argsort(keys, kind="stable"), kind="stable")


def spread_order(values: np.ndarray, alpha: float,
                 rng: np.random.Generator) -> np.ndarray:
    return np.sort(values)[kronecker_ranks(len(values), alpha, rng)]


def _arrivals(rate: float, start: float, length: float,
              rng: np.random.Generator) -> np.ndarray:
    n = max(1, round(rate * length))
    gaps = -np.log1p(-_quantiles(n))
    gaps = spread_order(gaps * length / gaps.sum(), ALPHA["gaps"], rng)
    return start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def schedule(params: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list[Scheduled]:
    """Requests due from ``-ramp_s`` to ``seconds`` (the window's end)."""
    rate = float(rate or params["rate"])
    rng = np.random.default_rng(seed)
    due = np.concatenate([
        _arrivals(rate, -params["ramp_s"], params["ramp_s"], rng),
        _arrivals(rate, 0.0, seconds, rng)])
    n = len(due)
    plens = spread_order(_lognormal_lengths(params["prompt"], n,
                                            params["granule"]),
                         ALPHA["prompt"], rng)
    outs = spread_order(_lognormal_lengths(params["output"], n),
                        ALPHA["output"], rng)
    shared = np.zeros(n, bool)
    sp = params.get("shared_prefix")
    system: tuple[int, ...] = ()
    if sp:
        system = tuple(int(t) for t in rng.integers(1, vocab, sp["tokens"]))
        eligible = np.flatnonzero(plens > sp["tokens"])
        k = min(len(eligible), round(sp["share"] * n))
        ranks = kronecker_ranks(len(eligible), ALPHA["shared"], rng)
        shared[eligible[ranks < k]] = True
    out = []
    for i in range(n):
        head = system if shared[i] else ()
        tail = rng.integers(1, vocab, int(plens[i]) - len(head))
        out.append(Scheduled(uid=i, due=float(due[i]),
                             prompt=head + tuple(int(t) for t in tail),
                             max_new_tokens=int(outs[i]),
                             shared=bool(shared[i])))
    return out


def max_len(params: dict) -> int:
    """The longest request the mix can make, prompt and output."""
    g = params["granule"]
    return (math.ceil(params["prompt"]["max"] / g) * g
            + params["output"]["max"])


def prompt_lengths(reqs) -> list[int]:
    return sorted({len(r.prompt) for r in reqs})
