"""device_idle_share.serve: percent of the traced serving window in which
no operation ran on the chip (profiler trace, averaged over chips).
Moves itl_p95_ms."""

from bench.core.trace import idle_share


def read(rec):
    return idle_share(rec["trace"])
