"""Prefill FLOPs in the traced part of the window over the device time of
the prefill program in the same part, as a share of the chip's peak bf16
FLOP/s."""
from bench.metrics._common import device_trace, prefill_flops, program_seconds

MARKER = "_prefill_one_fn"


def read(run):
    if device_trace(run, "prefill_mfu") is None:
        return None
    flops, n = prefill_flops(run)
    device_s = program_seconds(run, MARKER)
    if not n or device_s <= 0:
        return None
    return flops / device_s / run.peaks.flops * 100.0
