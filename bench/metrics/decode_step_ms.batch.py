"""Median engine `decode_step` span (it ends after the step's host sync)."""
from bench.metrics._common import decode_step_ms


def read(run):
    return decode_step_ms(run)
