"""90th percentile of time to first token over the requests due in the
window. A per-layer metric: the code cell completes under 100 requests in
a window, too few for a tail that a bound could hold."""
from bench.metrics._common import ttft_ms


def read(run):
    return ttft_ms(run, 90)
