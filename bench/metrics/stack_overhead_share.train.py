"""stack_overhead_share.train: percent of busy device time that runs
under the program's ``stack`` scope (``models/lm.py::run_stack``) and
outside every unit's sublayer scope: the layer scan's slicing of stacked
weights and saved activations, its carries and loop control, in every
phase (self time of each op, profiler trace, averaged over chips).
Moves train_tokens_per_s."""

from bench.core import scopes


def read(rec):
    got = scopes.rec_table(rec)
    if got is None or scopes.OVERHEAD not in got[0]:
        return None
    rows, busy = got
    return 100.0 * sum(rows[scopes.OVERHEAD].values()) / busy
