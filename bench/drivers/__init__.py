"""One driver per path the benchmark times: ``serve`` (the continuous-
batching engine under paced traffic) and ``train`` (the jitted train
step under a resolved plan).  A traffic mix names its driver.

Each exposes ``run(cell, opts, t_start) -> dict`` with the keys
``attempted``, ``failed``, ``metrics`` (end-to-end values by name),
``readings`` (the numbers compared against ``bench/limits``),
``record`` (what the per-layer readers read), ``memory_peak_bytes``
and, where asked, ``control``.  ``opts.fault`` plants one of the faults
the tests drive (``FAULTS``) in the timed path.
"""
