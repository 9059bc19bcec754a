"""The reduction of a trace by program span (`metrics/_program_trace.py`)
against a trace recorded on one TPU v5e (`data/program.xplane.pb`, made by
`record_program_trace.py`): a tiny model served through `Gateway` with a
`Trace` attached, two admissions and a few decode steps inside
`bench.window`."""

from __future__ import annotations

import math

import numpy as np
import pytest

from conftest import DATA

from bench.metrics import _program_trace as pt

TRACE = str(DATA / "program.xplane.pb")


@pytest.fixture(scope="module")
def idle():
    return pt.program_idle(TRACE)


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
    assert pt.program_idle(str(DATA / "small.xplane.pb")) is None
