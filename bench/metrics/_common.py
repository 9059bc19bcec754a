"""Arithmetic the metric readers share."""

from __future__ import annotations

import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.stats import percentile  # noqa: E402


def ttft_ms(run, pct: float) -> float | None:
    """Nearest-rank percentile of time to first token over every request
    due in the window, from its due time. A request refused, or without a
    first token by the end of the drain, counts at the drain's end: a
    lower bound of what it missed by."""
    vals = []
    for g in run.due_in_window:
        t = g.token_times[0] if g.token_times else run.drain_end
        vals.append(t - g.arrival_s)
    return percentile(vals, pct) * 1e3 if vals else None


def itl_ms(run, pct: float) -> float | None:
    """Nearest-rank percentile of every gap between consecutive output
    tokens of a request, both inside the window."""
    gaps = []
    for g in run.requests:
        ts = [t for t in g.token_times if run.window0 <= t <= run.window1]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return percentile(gaps, pct) * 1e3 if gaps else None


def decode_step_ms(run) -> float | None:
    spans = run.spans_in_window("decode_step")
    if not spans:
        return None
    return statistics.median(e - s for s, e, _ in spans) * 1e3


def device_trace(run, name: str):
    """The run's reduced trace (`xplane.Reduced`) where a device-trace
    metric may be read from it; None, with the reason on standard error,
    where the run has no trace, the device plane holds less than
    `xplane.MIN_TRACED_S` of the window, or its decode runs and syncs do
    not pair."""
    r = run.reduced
    if r is None:
        return None
    why = r.span.unreadable()
    if why:
        print(f"metric {name}: not read from the trace: {why}",
              file=sys.stderr)
        return None
    return r


def idle_share(run, name: str) -> float | None:
    """Share of the traced part of the window with no operation on the
    device."""
    r = device_trace(run, name)
    if r is None:
        return None
    return (1.0 - r.busy_s / r.traced_s) * 100.0


def decode_steps(run) -> list[tuple[float, float]]:
    """(FLOPs, bytes) of each decode step in the traced part of the
    window. A step's span records each live slot's position after it, so
    the token it processed sat one position earlier."""
    counts, conf = run.counts(), run.spec.conf
    return [counts.decode_step(conf, [p - 1 for p in attrs["positions"]])
            for _, _, attrs in run.spans_in_trace("decode_step")]


def prefill_flops(run) -> tuple[float, int]:
    counts = run.counts()
    spans = run.spans_in_trace("prefill_step")
    return (sum(counts.prefill(run.spec.conf, a["prompt_len"])[0]
                for _, _, a in spans), len(spans))


def program_seconds(run, marker: str) -> float:
    """Device seconds of every run of the programs whose name holds
    `marker`, inside the traced part of the window."""
    r = run.reduced
    return sum(s for name, s in r.program_s.items() if marker in name)
