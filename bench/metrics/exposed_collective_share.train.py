"""exposed_collective_share.train: percent of busy device time spent in
collectives (all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all) while no other operation ran on the same chip, over the
traced window, averaged over the chips (profiler trace).  Each op is
classed by its instruction name (a fusion that reads a collective's
result is compute); a loop or a call spans the ops it runs, so an op
with others nested in it is compute only where it is a collective.
Where no collective runs it reads 0.  Moves train_tokens_per_s."""

from bench.core.trace import COLLECTIVE, busy_ns, clip, short_name, union


def without_containers(ops):
    """One chip's ops by instruction name, less the loops and calls:
    the ops other than collectives that have an op nested in them."""
    named = [(short_name(o[0]),) + tuple(o[1:]) for o in ops]
    outer = set()
    open_: list[int] = []
    for i in sorted(range(len(named)),
                    key=lambda i: (named[i][1], -named[i][2])):
        while open_ and named[open_[-1]][2] <= named[i][1]:
            open_.pop()
        if open_ and named[i][2] <= named[open_[-1]][2]:
            outer.add(open_[-1])
        open_.append(i)
    return [o for i, o in enumerate(named)
            if i not in outer or COLLECTIVE.search(o[0])]


def exposed_ns(ops, lo: int, hi: int) -> int:
    """``bench.core.trace.exposed_collective_ns`` in one sweep over the
    two sorted unions (that function compares every pair of intervals:
    minutes on a 4-chip trace of a 16-layer step)."""
    coll = clip(union(o[1:3] for o in ops if COLLECTIVE.search(o[0])), lo, hi)
    comp = union(o[1:3] for o in ops if not COLLECTIVE.search(o[0]))
    exposed, j = 0, 0
    for s, e in coll:
        while j < len(comp) and comp[j][1] <= s:
            j += 1
        covered, k = 0, j
        while k < len(comp) and comp[k][0] < e:
            covered += min(e, comp[k][1]) - max(s, comp[k][0])
            k += 1
        exposed += (e - s) - covered
    return exposed


def read(rec):
    t = rec["trace"]
    if t is None or not t.device_ops or not t.host_spans:
        return None
    lo, hi = t.window
    shares = []
    for ops in t.device_ops:
        busy = busy_ns(ops, lo, hi)
        if busy:
            shares.append(exposed_ns(without_containers(ops), lo, hi) / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
