"""What every run needs around the program: the compile cache, a count of
compilations, the device's description and peak memory, host spans, and
a profiler session in a scratch directory."""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import time
from collections import Counter
from pathlib import Path

from .spec import ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = ROOT / ".jax_cache"


def enable_compile_cache() -> Path:
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache`` (the path the
    program's drivers use too).  Every program is cached, and nothing is
    evicted, so the second run of a cell compiles nothing."""
    import jax

    path = Path(os.environ.get(CACHE_ENV) or CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCount:
    """Counts XLA compilations and persistent-cache hits, split by
    whether the measured window was open."""

    def __init__(self):
        import jax

        self.in_window = False
        self.counts: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _key(self, what: str) -> str:
        return f"{what}_{'window' if self.in_window else 'setup'}"

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts[self._key("compiles")] += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts[self._key("cache_hits")] += 1


class SetupParts:
    """Seconds between named points of set-up, from the process's start:
    where ``setup_s`` goes, printed so that a run whose set-up jumps
    shows which part moved."""

    def __init__(self, t_start: float):
        self.last, self.parts = t_start, {}

    def __call__(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = now - self.last
        self.last = now


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def span(name: str):
    """A host span on the profiler's clock (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """One profiler session writing under ``$TMPDIR``; ``stop`` returns
    the directory, which ``cleanup`` removes."""

    def __init__(self):
        self.dir: Path | None = None
        self.on = False
        self.started = self.stopped = 0.0

    def start(self) -> None:
        import jax

        self.dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
        jax.profiler.start_trace(str(self.dir))
        self.on, self.started = True, time.perf_counter()

    def stop(self) -> Path:
        import jax

        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()
        self.on = False
        return self.dir

    def cleanup(self) -> None:
        if self.on:
            with contextlib.suppress(Exception):
                self.stop()
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
