"""Share of its roofline that the decode program reaches: the least time
of the decode steps in the traced part of the window, each the larger of
its FLOPs over peak FLOP/s and its bytes over peak bytes/s (bytes bound
it), over the device time of the decode program in the same part."""
from bench.metrics._common import decode_steps, device_trace, program_seconds
from bench.xplane import DECODE_MARKER


def read(run):
    if device_trace(run, "decode_roofline") is None:
        return None
    device_s = program_seconds(run, DECODE_MARKER)
    steps = decode_steps(run)
    if device_s <= 0 or not steps:
        return None
    pk = run.peaks
    least = sum(max(f / pk.flops, b / pk.hbm_bytes_per_s) for f, b in steps)
    return least / device_s * 100.0
