"""Share of the traced part of the window in which no operation runs on
the device."""
from bench.metrics._common import idle_share


def read(run):
    return idle_share(run, "device_idle_share.batch")
