"""Per-layer metric readers, one file each (``metrics/<metric>.py``),
found by the metric's name in ``BENCHMARK.json``.

Each defines ``read(run) -> float | None``, where ``run`` is the traced
run's :class:`bench.harness.Run`: its window queries with their stats,
its recorder spans and its trace reduction.  A reader that finds nothing
to read returns ``None``, and the harness leaves the metric out.
"""
