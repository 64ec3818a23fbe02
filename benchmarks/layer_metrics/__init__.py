"""One reader per per-layer metric, found by the metric's name in
``BENCHMARK.json``: ``read(ctx) -> float | None``. ``ctx`` is what a traced
run gathered (see ``run.py``): the load generator's records and header,
counter snapshots and the Prometheus text before and after the window,
pool samples, the reduced profiler trace with the traced interval, the
program's flight records and request traces, the configuration and
traffic files. A reader that finds nothing to read returns None and the
metric is left out of the line."""
