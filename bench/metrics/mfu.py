"""Model FLOPs of every prefill and decode token in the traced part of the
window over that part's length times the chip's peak bf16 FLOP/s."""
from bench.metrics._common import decode_steps, device_trace, prefill_flops


def read(run):
    r = device_trace(run, "mfu")
    if r is None or run.spans is None:
        return None
    flops = sum(f for f, _ in decode_steps(run)) + prefill_flops(run)[0]
    if flops <= 0:
        return None
    return flops / (r.traced_s * run.peaks.flops) * 100.0
