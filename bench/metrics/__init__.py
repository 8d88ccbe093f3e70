"""Per-layer readers, one file per metric named in BENCHMARK.json.

Each module defines ``read(w) -> float | None``, where ``w`` is the run's
``Window`` (``run.py``): counter deltas over the window, the benchmark's
host spans, and with ``--trace 1`` the reduced device trace.  A reader
that finds nothing to read returns ``None`` and the metric is left out.
"""
