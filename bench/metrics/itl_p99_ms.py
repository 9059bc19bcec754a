"""99th percentile of the gaps between consecutive output tokens."""
from bench.metrics._common import itl_ms


def read(run):
    return itl_ms(run, 99)
