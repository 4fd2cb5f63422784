"""On-chip benchmark of the repo's training and serving paths.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by
the name ``BENCHMARK.json`` gives it.
"""
