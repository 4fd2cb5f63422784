"""engine_step_ms.serve: median host wall time of one ``engine.step``
call in the window (the harness's span around it: admission, scheduling,
the device step and the token fetch).  Moves itl_p95_ms."""

import statistics


def read(rec):
    walls = [s["te"] - s["ts"] for s in rec["steps"]]
    return 1e3 * statistics.median(walls) if walls else None
