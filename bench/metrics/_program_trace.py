"""What the readers of the program's own spans share: the serving loop's
events inside the window, and the run's profiler trace re-read with the
program's `serve.*` host annotations.

The trace's host and device clocks differ by a millisecond or two, so
`program_idle` first estimates their offset from the host syncs: each
`serve.decode_sync` annotation ends after the decode program run it
waits on (runs and syncs paired in order). The offset is the largest,
over the syncs in the window, of the program run's end less the sync's
end: the shift that puts every run's end at or before its sync, and the
run that the host saw soonest at its sync. (The median of the same
differences, also printed, leaves half the runs ending after their syncs,
by up to 0.33 ms in a TPU v5e trace.) Device intervals are shifted by it
before each idle gap is labelled by the innermost `serve.*` annotation
open at its middle.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import xplane  # noqa: E402

#: the decode program, whose runs the host's `serve.decode_sync` waits on
DECODE_MARKER = "_decode_step_fn"
SYNC = "serve.decode_sync"
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
    """Device idle time in the window by program span. Seconds."""
    offset_s: float                    # device clock less host clock
    median_offset_s: float             # the median estimate, for comparison
    window_s: float
    idle_s: float                      # mean over chips
    idle_by_label: dict[str, float]    # innermost `serve.*` span -> idle


def program_idle(path: str, window: str = "bench.window"
                 ) -> ProgramIdle | None:
    """Idle time of the trace at `path` inside the host annotation named
    `window`, labelled by program span after the clock correction; None
    when the trace holds no `serve.decode_sync` or the decode program
    runs do not pair with the syncs one to one."""
    from jax.profiler import ProfileData

    labels: list[tuple[int, int, str]] = []
    devices = []                   # per device: (op intervals, decode ends)
    edges = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            ops, ends = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    ends.extend(ev.start_ns + ev.duration_ns
                                for ev in line.events
                                if DECODE_MARKER in ev.name)
            devices.append((ops, sorted(ends)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        labels.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name == window and edges is None:
                        edges = (ev.start_ns, ev.start_ns + ev.duration_ns)
    syncs = sorted(e for _, e, name in labels if name == SYNC)
    if edges is None or not devices or not syncs:
        return None
    w0, w1 = edges
    idle: dict[str, float] = collections.defaultdict(float)
    offsets, medians = [], []
    for ops, ends in devices:
        if len(ends) != len(syncs):
            print(f"program trace: {len(ends)} decode program runs against "
                  f"{len(syncs)} {SYNC} spans; not paired", file=sys.stderr)
            return None
        diffs = [m - s for m, s in zip(ends, syncs) if w0 <= s <= w1]
        if not diffs:
            return None
        off = max(diffs)
        offsets.append(off)
        medians.append(statistics.median(diffs))
        gaps, prev = [], w0
        for s, e in xplane._union((s - off, e - off) for s, e in ops):
            if e <= w0:
                continue
            if s >= w1:
                break
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if prev < w1:
            gaps.append((prev, w1))
        for (s, e), name in zip(gaps, xplane._labels_at(
                [(s + e) / 2 for s, e in gaps], labels)):
            idle[name] += (e - s) * 1e-9
    n = len(devices)
    return ProgramIdle(
        offset_s=statistics.median(offsets) * 1e-9,
        median_offset_s=statistics.median(medians) * 1e-9,
        window_s=(w1 - w0) * 1e-9,
        idle_s=sum(idle.values()) / n,
        idle_by_label={k: v / n for k, v in
                       sorted(idle.items(), key=lambda kv: -kv[1])})


def run_program_idle(run) -> ProgramIdle | None:
    """`program_idle` of the run's own trace, in `.bench_profile` of the
    checkout, printed to standard error; None without a trace of this
    run's window (its length must equal the harness's reading)."""
    if run.reduced is None:
        return None
    try:
        path = xplane.find_xplane(
            str(run.spec.bench_dir.parent / ".bench_profile"))
    except FileNotFoundError:
        return None
    pi = program_idle(path)
    if pi is None or pi.window_s != run.reduced.window_s:
        return None
    under = sum(v for k, v in pi.idle_by_label.items() if k != NO_LABEL)
    print(f"program trace: clock offset {pi.offset_s * 1e3!r} ms (median "
          f"estimate {pi.median_offset_s * 1e3!r} ms); idle {pi.idle_s!r} "
          f"s of {pi.window_s!r}, "
          f"{under / pi.idle_s * 100 if pi.idle_s else 0.0!r}% under a "
          f"program span", file=sys.stderr)
    for name, s in pi.idle_by_label.items():
        print(f"program trace: idle {s!r} s under {name}", file=sys.stderr)
    return pi
