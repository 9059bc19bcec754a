"""Metric readers, one file per metric named as in `BENCHMARK.json`. Each
defines `read(run) -> float | None` over a `harness.Run`; None means the
run holds nothing to read, and the metric is left out of the line."""
