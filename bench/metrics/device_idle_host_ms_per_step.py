"""Device idle time in the window under a program span other than
`decode_sync` (host work the device waited on), in ms per decode step,
after the trace's host and device clocks are aligned."""
from bench.metrics._program_trace import SYNC, run_program_idle


def read(run):
    pi = run_program_idle(run)
    if pi is None or not run.steps:
        return None
    host = sum(s for name, s in pi.idle_by_label.items()
               if name.startswith("serve.") and name != SYNC)
    return host / run.steps * 1e3
