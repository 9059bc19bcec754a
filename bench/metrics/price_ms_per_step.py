"""Plan-cache consults (`price` spans, a planner solve included on a
miss) in the window, in ms per decode step."""
from bench.metrics._program_trace import events_in_window


def read(run):
    spans = events_in_window(run, "price")
    if not spans or not run.steps:
        return None
    return sum(e - s for s, e, _ in spans) / run.steps * 1e3
