"""The reduction of a trace by program span (`metrics/_program_trace.py`)
against a trace recorded on one TPU v5e (`data/program.xplane.pb`, made by
`record_program_trace.py`): a tiny model served through `Gateway` with a
`Trace` attached, two admissions and a few decode steps inside
`bench.window`; and the same trace with its device plane cut partway, as
a profiler whose buffers fill leaves it."""

from __future__ import annotations

import json
import math
import types

import numpy as np
import pytest

from conftest import DATA, ROOT

from bench import harness, xplane
from bench.metrics import _program_trace as pt

TRACE = str(DATA / "program.xplane.pb")


@pytest.fixture(scope="module")
def events():
    return xplane.read_events(TRACE)


@pytest.fixture(scope="module")
def idle(events):
    return pt.program_idle(events)


@pytest.fixture(scope="module")
def raw():
    """Window edges, op intervals, decode program ends and sync ends (ns),
    read here without the module under test."""
    from jax.profiler import ProfileData
    ops, ends, syncs, window = [], [], [], None
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((s, t))
                elif (plane.name == "/device:TPU:0"
                      and line.name == "XLA Modules"
                      and "_decode_step_fn" in e.name):
                    ends.append(t)
                elif e.name == "serve.decode_sync":
                    syncs.append(t)
                elif e.name == "bench.window":
                    window = (s, t)
    return window, ops, sorted(ends), sorted(syncs)


def test_offset_is_finite_and_small(idle, raw):
    _, _, ends, syncs = raw
    assert len(ends) == len(syncs) >= 3
    assert math.isfinite(idle.offset_s) and abs(idle.offset_s) < 5e-3


def test_no_decode_ends_after_its_sync_once_shifted(idle, raw):
    _, _, ends, syncs = raw
    off = idle.offset_s * 1e9
    late = [m - off - s for m, s in zip(ends, syncs)]
    assert max(late) <= 50e3, late
    # the median of the same differences is the later shift
    assert idle.median_offset_s <= idle.offset_s


def test_labelled_idle_sums_to_the_traces_idle(idle, raw):
    (w0, w1), ops, _, _ = raw
    off = idle.offset_s * 1e9
    # busy time by a sweep over +1/-1 edges of the shifted, clipped ops
    edges = sorted([(max(s - off, w0), 1) for s, e in ops if e - off > w0
                    and s - off < w1]
                   + [(min(e - off, w1), -1) for s, e in ops if e - off > w0
                      and s - off < w1])
    times = np.array([t for t, _ in edges])
    depth = np.cumsum([d for _, d in edges])
    busy = float(np.sum(np.diff(times)[depth[:-1] > 0]))
    assert idle.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert idle.idle_s == pytest.approx((w1 - w0 - busy) * 1e-9, rel=1e-6)
    assert sum(idle.idle_by_label.values()) == pytest.approx(idle.idle_s,
                                                             rel=1e-6)
    assert 0 < idle.idle_s < idle.window_s
    assert all(k.startswith("serve.") or k == pt.NO_LABEL
               for k in idle.idle_by_label)
    assert "serve.gateway_step" in idle.idle_by_label


def test_a_trace_without_program_spans_reads_nothing():
    assert pt.program_idle(
        xplane.read_events(str(DATA / "small.xplane.pb"))) is None


def _cut(events, at_ns):
    """The trace as a profiler whose device buffers filled at `at_ns`
    would leave it: every device event that ends later dropped, the
    host's annotations kept."""
    return xplane.Events(
        [xplane.Device([o for o in d.ops if o[1] <= at_ns],
                       [m for m in d.modules if m[1] <= at_ns])
         for d in events.devices], list(events.host))


@pytest.fixture
def cut(events, raw, monkeypatch):
    # the recorded window is 27 ms long, its syncs 1.6-2 ms after their
    # runs: at this scale a sync 1 ms past the plane's end marks the cut
    monkeypatch.setattr(xplane, "CUT_SLACK_NS", 1_000_000)
    _, _, ends, _ = raw
    return _cut(events, (ends[0] + ends[1]) // 2)


def _busy_ns(ops, w0, w1, shift=0):
    """Busy time of [w0, w1] by a sweep over +1/-1 edges of the shifted,
    clipped ops."""
    kept = [(max(s - shift, w0), min(e - shift, w1)) for s, e in ops
            if e - shift > w0 and s - shift < w1]
    edges = sorted([(s, 1) for s, _ in kept] + [(e, -1) for _, e in kept])
    times = np.array([t for t, _ in edges])
    depth = np.cumsum([d for _, d in edges])
    return float(np.sum(np.diff(times)[depth[:-1] > 0]))


def test_a_cut_plane_is_read_over_the_span_it_holds(events, cut, raw):
    (w0, w1), ops, _, _ = raw
    whole = xplane.reduce_trace(events)
    r = xplane.reduce_trace(cut)
    end = cut.devices[0].end_ns()
    assert r.span.cut and not whole.span.cut
    assert whole.traced_s == whole.window_s
    assert r.window_s == whole.window_s
    assert r.traced_s == pytest.approx((end - w0) * 1e-9)
    assert r.traced_s < r.window_s
    # the whole trace's ops over the covered span, counted independently
    assert r.busy_s == pytest.approx(_busy_ns(ops, w0, end) * 1e-9, rel=1e-6)
    assert sum(s for _, s in r.idle_by_label) == pytest.approx(
        r.traced_s - r.busy_s, rel=1e-6)
    assert r.program_runs["jit__decode_step_fn"] == 1
    assert whole.program_runs["jit__decode_step_fn"] == 3
    # one run, paired with the first of the three syncs
    assert r.span.decode_runs == 1 and r.span.why == ""


def test_program_idle_of_a_cut_plane_reads_the_covered_span(cut, raw):
    (w0, _), ops, ends, syncs = raw
    pi = pt.program_idle(cut)
    assert pi.decode_runs == 1
    off = pi.offset_s * 1e9
    assert off == pytest.approx(ends[0] - syncs[0])
    end = cut.devices[0].end_ns() - off          # on the host's clock
    busy = _busy_ns(ops, w0, end, shift=off)
    assert pi.idle_s == pytest.approx((end - w0 - busy) * 1e-9, rel=1e-6)
    assert pi.idle_s < pt.program_idle(xplane.read_events(TRACE)).idle_s


def _device_trace_metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in spec["end_to_end"] + spec["per_layer"]
            if m["source"] == "device_trace"]


def _unpaired(events):
    """The trace with its second decode sync taken out."""
    syncs = sorted(h for h in events.host if h[2] == xplane.DECODE_SYNC)
    return xplane.Events(events.devices,
                         [h for h in events.host if h != syncs[1]])


@pytest.mark.parametrize("case", ["whole", "cut", "unpaired"])
def test_device_trace_metrics_read_nothing_from_an_unreadable_trace(
        events, cut, case, capsys):
    ev = {"whole": events, "cut": cut, "unpaired": _unpaired(events)}[case]
    reduced = xplane.reduce_trace(ev)
    why = reduced.span.unreadable()
    assert why
    names = _device_trace_metrics()
    assert "device_idle_host_ms_per_step" in names and len(names) >= 5
    for name in names:
        mod = harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")
        run = types.SimpleNamespace(reduced=reduced, trace_events=ev,
                                    spans=[], steps=3)
        assert mod.read(run) is None, name
        err = capsys.readouterr().err
        assert f"metric {name}: not read from the trace: {why}" in err


def test_unpaired_runs_and_syncs_are_named(events, monkeypatch):
    monkeypatch.setattr(xplane, "MIN_TRACED_S", 0.001)
    span = xplane.trace_span(_unpaired(events))
    assert span.why == (f"3 decode program runs against 2 "
                        f"{xplane.DECODE_SYNC} spans; not paired")
    assert span.unreadable() == span.why


def test_device_trace_metrics_read_the_covered_span_above_the_minimum(
        cut, raw, monkeypatch):
    monkeypatch.setattr(xplane, "MIN_TRACED_S", 0.001)
    r = xplane.reduce_trace(cut)
    run = types.SimpleNamespace(reduced=r, trace_events=cut, spans=[],
                                steps=3)
    metric = lambda name: harness.load_module(
        ROOT / "bench" / "metrics" / f"{name}.py").read(run)
    assert metric("device_idle_share.batch") == pytest.approx(
        (1 - r.busy_s / r.traced_s) * 100)
    pi = pt.program_idle(cut)
    host = sum(s for k, s in pi.idle_by_label.items()
               if k.startswith("serve.") and k != pt.SYNC)
    assert metric("device_idle_host_ms_per_step") == pytest.approx(
        host * 1e3)
