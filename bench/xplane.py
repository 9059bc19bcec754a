"""Reduction of a profiler trace (`.xplane.pb`) to device busy and idle
time, device time per jitted program, the costliest device operations,
and idle gaps labelled by the host annotation open at the time.

Device planes are those named `/device:TPU:<n>`. On each, the line
`XLA Ops` holds one event per operation run, named by its HLO text (kept
here up to " = "), and `XLA Modules` one per program run. Host annotations (`jax.profiler.TraceAnnotation`) are the
events on the host planes whose names start with a given prefix; one of
them marks the measured window. An idle gap is labelled by the innermost
other annotation open at its middle, "(no host annotation)" where none
is. The trace's host and device clocks differ by about a millisecond (a
TPU v5e trace shows programs starting 1.2 ms before the host call that
launched them), so a label is right to that scale.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Reduced:
    """What a run reads from its trace. Times in seconds."""
    window_s: float
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


def reduce_trace(path: str, window: str = "bench.window",
                 label_prefix: str = "bench.") -> Reduced:
    """Reduce the trace at `path` over the host annotation named `window`
    (the measured window), in one pass over the file."""
    from jax.profiler import ProfileData

    labels: list[tuple[float, float, str]] = []
    devices = []                       # per device: (ops, modules)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is not None:
                    dst.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name) for ev in line.events)
            devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                labels.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name) for ev in line.events
                              if ev.name.startswith(label_prefix))
    edges = [(s, e) for s, e, name in labels if name == window]
    if not edges:
        raise LookupError(f"no host annotation {window!r} in {path}")
    w0, w1 = edges[0]

    def clip(events):
        return [(max(s, w0), min(e, w1), name) for s, e, name in events
                if e > w0 and s < w1]

    busy = 0.0
    program_s: dict[str, float] = collections.defaultdict(float)
    program_runs: dict[str, int] = collections.defaultdict(int)
    op_s: dict[str, float] = collections.defaultdict(float)
    gaps: list[tuple[float, float]] = []
    for ops, modules in devices:
        ops = clip(ops)
        for s, e, name in ops:
            op_s[name.split(" = ")[0].lstrip("%")] += (e - s) * 1e-9
        for s, e, name in clip(modules):
            name = name.split("(")[0]
            program_s[name] += (e - s) * 1e-9
            program_runs[name] += 1
        merged = _union((s, e) for s, e, _ in ops)
        busy += sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if prev < w1:
            gaps.append((prev, w1))
    idle: dict[str, float] = collections.defaultdict(float)
    inner = [lab for lab in labels if lab[2] != window]
    for (s, e), name in zip(gaps, _labels_at([(s + e) / 2 for s, e in gaps],
                                             inner)):
        idle[name] += (e - s) * 1e-9
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / max(len(devices), 1),
        program_s=dict(program_s), program_runs=dict(program_runs),
        top_ops=sorted(op_s.items(), key=lambda kv: -kv[1])[:10],
        idle_by_label=sorted(idle.items(), key=lambda kv: -kv[1])[:10],
        n_devices=len(devices))


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
