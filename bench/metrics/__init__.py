"""Per-layer metric readers: one module per metric, named as the metric,
each with ``read(ctx) -> float | None``. A reader that finds nothing to
read returns None, and the harness leaves the metric out of the line.
``ctx`` is ``bench.harness.Context``."""
