"""Median over the window's decode steps of the engine's host time:
`decode_prep` (live mask, key split), `decode_step` less its
`decode_sync` (the dispatch), and `decode_record` (per-slot tokens)."""
import collections
import statistics

from bench.metrics._program_trace import events_in_window

KINDS = ("decode_prep", "decode_step", "decode_sync", "decode_record")


def read(run):
    steps = collections.defaultdict(dict)
    for kind in KINDS:
        for s, e, ev in events_in_window(run, kind):
            steps[ev.name][kind] = e - s
    host = [p["decode_prep"] + p["decode_step"] - p["decode_sync"]
            + p["decode_record"] for p in steps.values() if len(p) == 4]
    return statistics.median(host) * 1e3 if host else None
