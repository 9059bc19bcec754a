"""Median time to first token over the requests due in the window."""
from bench.metrics._common import ttft_ms


def read(run):
    return ttft_ms(run, 50)
