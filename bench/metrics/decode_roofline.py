"""Share of its roofline that the decode program reaches: the least time
of the window's decode steps, each the larger of its FLOPs over peak
FLOP/s and its bytes over peak bytes/s (bytes bound it), over the device
time of the decode program in the trace."""
from bench.metrics._common import decode_steps, program_seconds

MARKER = "_decode_step_fn"


def read(run):
    if run.reduced is None:
        return None
    device_s = program_seconds(run, MARKER)
    steps = decode_steps(run)
    if device_s <= 0 or not steps:
        return None
    pk = run.peaks
    least = sum(max(f / pk.flops, b / pk.hbm_bytes_per_s) for f, b in steps)
    return least / device_s * 100.0
