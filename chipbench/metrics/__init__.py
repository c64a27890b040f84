"""Per-layer metric readers, one module per metric of BENCHMARK.json's
``per_layer``.  Each has ``read(run) -> float | None``: the metric from
the run's spans, counters or reduced trace, or ``None`` where the run
has nothing to read (the harness then leaves the metric out)."""
