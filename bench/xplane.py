"""Reduction of a profiler trace (`.xplane.pb`) to device busy and idle
time, device time per jitted program, the costliest device operations,
and idle gaps labelled by the host annotation open at the time.

Device planes are those named `/device:TPU:<n>`. On each, the line
`XLA Ops` holds one event per operation run, named by its HLO text (kept
here up to " = "), and `XLA Modules` one per program run. Host annotations
(`jax.profiler.TraceAnnotation`) are the events on the host planes whose
names start with `bench.` (the harness's own; one of them marks the
measured window) or `serve.` (the program's spans). An idle gap is
labelled by the innermost other annotation open at its middle, "(no host
annotation)" where none is. The trace's host and device clocks differ by
about a millisecond (a TPU v5e trace shows programs starting 1.2 ms
before the host call that launched them), so a label is right to that
scale.

The profiler keeps a device's events in a fixed set of buffers, and once
they are full it drops the rest: the device plane then ends before the
window does. A host `serve.decode_sync` or `serve.prefill_sync` that
ends well after the plane's last event waited on a program the plane
does not hold, so the plane was cut there. Every reading is then taken
over the part of the window that the plane holds, `[window start, plane
end]` (`traced_s`), never over the whole window.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import statistics

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIXES = ("bench.", "serve.")
#: the decode program, whose runs the host's `serve.decode_sync` waits on
DECODE_MARKER = "_decode_step_fn"
DECODE_SYNC = "serve.decode_sync"
SYNCS = (DECODE_SYNC, "serve.prefill_sync")
#: a sync that ends this long after a device plane's last event waited on
#: a program that the plane does not hold (a sync ends 1.6-2 ms after its
#: program in a TPU v5e trace; a decode step takes 15-60 ms)
CUT_SLACK_NS = 10_000_000
#: the most by which a decode run may end after its sync, less the median
#: of the same differences, in a trace whose runs and syncs pair (a TPU v5e
#: trace reads 0.33 ms)
PAIR_SLACK_NS = 5_000_000
#: device-trace metrics are read only where the plane holds this much of
#: the window
MIN_TRACED_S = 15.0


@dataclasses.dataclass
class Device:
    """One device plane's events, each (start_ns, end_ns, name)."""
    ops: list
    modules: list

    def end_ns(self) -> int | None:
        """Where the plane's events stop: the earlier of its two lines'
        last ends, so that both are whole before it."""
        ends = [max(e for _, e, _ in line) for line in (self.ops, self.modules)
                if line]
        return min(ends) if ends else None


@dataclasses.dataclass
class Events:
    """What the readers take from a trace file, in one pass over it."""
    devices: list[Device]
    host: list[tuple[int, int, str]]   # annotations named HOST_PREFIXES


def read_events(path: str) -> Events:
    from jax.profiler import ProfileData

    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = Device([], [])
            for line in plane.lines:
                dst = {"XLA Ops": dev.ops,
                       "XLA Modules": dev.modules}.get(line.name)
                if dst is not None:
                    dst.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events
                            if ev.name.startswith(HOST_PREFIXES))
    return Events(devices, host)


@dataclasses.dataclass
class Span:
    """The part of the window that every device plane holds, and the
    decode program runs in it paired with their syncs. Times in ns."""
    w0: int
    w1: int
    end: int                           # w1, or where a cut plane ends
    cut: bool
    decode_runs: int                   # per device, in the span
    offset_ns: int | None              # device less host clock, paired
    median_offset_ns: int | None
    why: str                           # "" where runs and syncs pair

    @property
    def traced_s(self) -> float:
        return (self.end - self.w0) * 1e-9

    def unreadable(self) -> str:
        """Why device-trace metrics cannot be read here; "" where they
        can."""
        if self.traced_s < MIN_TRACED_S:
            return (f"the device plane holds {self.traced_s!r} s of the "
                    f"window, under {MIN_TRACED_S} s")
        return self.why


def window_edges(events: Events, window: str) -> tuple[int, int]:
    for s, e, name in events.host:
        if name == window:
            return s, e
    raise LookupError(f"no host annotation {window!r} in the trace")


def trace_span(events: Events, window: str = "bench.window") -> Span:
    """Where the device planes stop inside the window, and whether the
    decode runs they hold pair one to one, in order, with the host's
    decode syncs: every sync where the plane is whole; the first syncs,
    one to each run, where it is cut."""
    w0, w1 = window_edges(events, window)
    syncs = sorted(e for _, e, name in events.host
                   if name in SYNCS and w0 <= e <= w1)
    decode_syncs = sorted(e for _, e, name in events.host
                          if name == DECODE_SYNC)
    end, cut = w1, False
    for dev in events.devices:
        plane_end = dev.end_ns()
        if plane_end is None:
            end = w0
        elif any(s > plane_end + CUT_SLACK_NS for s in syncs):
            end, cut = min(end, plane_end), True
    if not events.devices:
        end = w0
    span = Span(w0, w1, max(end, w0), cut, 0, None, None, "")
    offsets, medians = [], []
    for dev in events.devices:
        ends = sorted(e for s, e, name in dev.modules
                      if DECODE_MARKER in name and e <= span.end)
        span.decode_runs = len(ends)
        if not decode_syncs:
            span.why = f"the trace holds no {DECODE_SYNC}"
            return span
        if len(ends) > len(decode_syncs) or (
                not cut and len(ends) != len(decode_syncs)):
            span.why = (f"{len(ends)} decode program runs against "
                        f"{len(decode_syncs)} {DECODE_SYNC} spans; not "
                        f"paired")
            return span
        diffs = [r - s for r, s in zip(ends, decode_syncs)]
        inside = [d for d, s in zip(diffs, decode_syncs) if w0 <= s <= w1]
        if not inside:
            span.why = "no decode sync inside the window"
            return span
        late = max(diffs) - statistics.median(diffs)
        if late > PAIR_SLACK_NS:
            span.why = (f"decode runs and {DECODE_SYNC} spans out of step: "
                        f"a run ends {late * 1e-6!r} ms further past "
                        f"its sync than the median run")
            return span
        offsets.append(max(inside))
        medians.append(statistics.median(inside))
    if offsets:
        span.offset_ns = statistics.median(offsets)
        span.median_offset_ns = statistics.median(medians)
    return span


@dataclasses.dataclass
class Reduced:
    """What a run reads from its trace, over the traced part of the window.
    Times in seconds."""
    window_s: float
    traced_s: float                    # the part of the window the planes hold
    span: Span
    busy_s: float                      # union of op intervals, mean over chips
    program_s: dict[str, float]        # module name -> summed device time
    program_runs: dict[str, int]       # module name -> number of runs
    top_ops: list[tuple[str, float]]   # op name -> summed device time
    idle_by_label: list[tuple[str, float]]
    n_devices: int


def find_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _union(intervals):
    """Merged (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(merged, w0: int, w1: int) -> list[tuple[int, int]]:
    """The idle intervals of [w0, w1] between merged busy intervals."""
    out, prev = [], w0
    for s, e in merged:
        if e <= w0:
            continue
        if s >= w1:
            break
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if prev < w1:
        out.append((prev, w1))
    return out


def reduce_trace(events: Events, window: str = "bench.window",
                 label_prefix: str = "bench.") -> Reduced:
    """Reduce a trace's events over the traced part of the host annotation
    named `window` (the measured window)."""
    span = trace_span(events, window)
    w0, w1 = span.w0, span.end
    labels = [lab for lab in events.host if lab[2].startswith(label_prefix)
              and lab[2] != window]

    def clip(evs):
        return [(max(s, w0), min(e, w1), name) for s, e, name in evs
                if e > w0 and s < w1]

    busy = 0.0
    program_s: dict[str, float] = collections.defaultdict(float)
    program_runs: dict[str, int] = collections.defaultdict(int)
    op_s: dict[str, float] = collections.defaultdict(float)
    idle_gaps: list[tuple[int, int]] = []
    for dev in events.devices:
        ops = clip(dev.ops)
        for s, e, name in ops:
            op_s[name.split(" = ")[0].lstrip("%")] += (e - s) * 1e-9
        for s, e, name in clip(dev.modules):
            name = name.split("(")[0]
            program_s[name] += (e - s) * 1e-9
            program_runs[name] += 1
        merged = _union((s, e) for s, e, _ in ops)
        busy += sum(e - s for s, e in merged) * 1e-9
        idle_gaps.extend(gaps(merged, w0, w1))
    idle: dict[str, float] = collections.defaultdict(float)
    for (s, e), name in zip(idle_gaps, _labels_at(
            [(s + e) / 2 for s, e in idle_gaps], labels)):
        idle[name] += (e - s) * 1e-9
    return Reduced(
        window_s=(span.w1 - span.w0) * 1e-9, traced_s=span.traced_s,
        span=span, busy_s=busy / max(len(events.devices), 1),
        program_s=dict(program_s), program_runs=dict(program_runs),
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:10],
        idle_by_label=sorted(idle.items(), key=lambda kv: -kv[1])[:10],
        n_devices=len(events.devices))


def _labels_at(times, labels) -> list[str]:
    """For each time, the innermost (latest-starting) annotation open at
    it, in one sweep over both sorted lists; annotations nest."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    labels = sorted(labels)
    out = ["(no host annotation)"] * len(times)
    stack: list[tuple[float, float, str]] = []
    j = 0
    for i in order:
        t = times[i]
        while j < len(labels) and labels[j][0] <= t:
            while stack and stack[-1][1] < labels[j][0]:
                stack.pop()
            stack.append(labels[j])
            j += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if stack:
            out[i] = stack[-1][2]
    return out
