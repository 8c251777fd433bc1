"""One reader per metric, found by the metric's name in ``BENCHMARK.json``:
``read(ctx) -> float | None`` with ``ctx`` the harness's ``run.Context``.  A
reader that finds nothing to read returns None and the metric is left out."""
