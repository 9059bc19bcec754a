"""Device idle time in the traced part of the window under a program span
other than `decode_sync` (host work the device waited on), in ms per
decode program run in the same part, after the trace's host and device
clocks are aligned."""
from bench.metrics._program_trace import SYNC, run_program_idle


def read(run):
    pi = run_program_idle(run, "device_idle_host_ms_per_step")
    if pi is None or not pi.decode_runs:
        return None
    host = sum(s for name, s in pi.idle_by_label.items()
               if name.startswith("serve.") and name != SYNC)
    return host / pi.decode_runs * 1e3
