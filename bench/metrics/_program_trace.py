"""What the readers of the program's own spans share: the serving loop's
events inside the window, and the run's profiler trace read with the
program's `serve.*` host annotations.

The trace's host and device clocks differ by a millisecond or two, so
`program_idle` takes their offset from the host syncs
(`xplane.trace_span`): each `serve.decode_sync` annotation ends after the
decode program run it waits on (runs and syncs paired in order). The
offset is the largest, over the syncs in the window, of the program run's
end less the sync's end: the shift that puts every run's end at or before
its sync, and the run that the host saw soonest at its sync. (The median
of the same differences, also printed, leaves half the runs ending after
their syncs, by up to 0.33 ms in a TPU v5e trace.) Device intervals are
shifted by it before each idle gap of the traced part of the window is
labelled by the innermost `serve.*` annotation open at its middle.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import xplane  # noqa: E402

SYNC = xplane.DECODE_SYNC
NO_LABEL = "(no host annotation)"


def traced(run) -> bool:
    """True when the run's program records the serving loop's own spans;
    a program without them leaves every metric read here out."""
    return any(ev.kind == "decode_prep" for ev in run.spans or ())


def events_in_window(run, kind: str) -> list:
    """Program events of `kind` that lie inside the window, each as
    (start, end, event) in perf_counter seconds."""
    out = []
    for ev in run.spans or ():
        if ev.kind == kind:
            s, e = run.span_offset + ev.t0, run.span_offset + ev.t1
            if s >= run.window0 and e <= run.window1:
                out.append((s, e, ev))
    return out


@dataclasses.dataclass
class ProgramIdle:
    """Device idle time in the traced part of the window by program span.
    Seconds."""
    offset_s: float                    # device clock less host clock
    median_offset_s: float             # the median estimate, for comparison
    window_s: float
    traced_s: float                    # the part of the window read
    decode_runs: int                   # decode program runs in that part
    idle_s: float                      # mean over chips
    idle_by_label: dict[str, float]    # innermost `serve.*` span -> idle


def program_idle(events: xplane.Events, window: str = "bench.window"
                 ) -> ProgramIdle | None:
    """Idle time of a trace inside the traced part of the host annotation
    named `window` (`xplane.trace_span`), labelled by program span after
    the clock correction; None, with the reason on standard error, where
    the decode program runs in that part do not pair one to one with the
    host's `serve.decode_sync` spans."""
    span = xplane.trace_span(events, window)
    if span.offset_ns is None:
        print(f"program trace: {span.why}", file=sys.stderr)
        return None
    off, w0 = span.offset_ns, span.w0
    # a cut plane's end is on the device clock: the same instant on the
    # host's, as the shifted intervals are
    w1 = span.end - off if span.cut else span.end
    labels = [lab for lab in events.host if lab[2].startswith("serve.")]
    idle: dict[str, float] = collections.defaultdict(float)
    for dev in events.devices:
        gaps = xplane.gaps(xplane._union((s - off, e - off)
                                         for s, e, _ in dev.ops), w0, w1)
        for (s, e), name in zip(gaps, xplane._labels_at(
                [(s + e) / 2 for s, e in gaps], labels)):
            idle[name] += (e - s) * 1e-9
    n = len(events.devices)
    return ProgramIdle(
        offset_s=off * 1e-9, median_offset_s=span.median_offset_ns * 1e-9,
        window_s=(span.w1 - w0) * 1e-9, traced_s=span.traced_s,
        decode_runs=span.decode_runs, idle_s=sum(idle.values()) / n,
        idle_by_label={k: v / n for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1])})


def run_program_idle(run, name: str) -> ProgramIdle | None:
    """`program_idle` of the run's own trace, printed to standard error;
    None where metric `name` cannot be read from the run's trace
    (`_common.device_trace`)."""
    from bench.metrics._common import device_trace
    if device_trace(run, name) is None:
        return None
    pi = program_idle(run.trace_events)
    if pi is None:
        return None
    under = sum(v for k, v in pi.idle_by_label.items() if k != NO_LABEL)
    print(f"program trace: clock offset {pi.offset_s * 1e3!r} ms (median "
          f"estimate {pi.median_offset_s * 1e3!r} ms); idle {pi.idle_s!r} "
          f"s of {pi.traced_s!r} traced, "
          f"{under / pi.idle_s * 100 if pi.idle_s else 0.0!r}% under a "
          f"program span", file=sys.stderr)
    for name, s in pi.idle_by_label.items():
        print(f"program trace: idle {s!r} s under {name}", file=sys.stderr)
    return pi
