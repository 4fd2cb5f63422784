"""device_idle_share.train: percent of the traced training window in
which no operation ran on the chip (profiler trace, averaged over chips).
Moves train_tokens_per_s."""

from bench.core.trace import idle_share


def read(rec):
    return idle_share(rec["trace"])
