"""The trace reduction against a small trace recorded on one TPU v5e
(`data/small.xplane.pb`, made by `record_small_trace.py`): three rounds of
one `_prefill_one_fn` run inside `bench.admit` and four `_decode_step_fn`
runs inside `bench.step`, then a sleep inside `bench.wait`, all inside
`bench.window`."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import DATA

from bench import xplane

TRACE = str(DATA / "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(xplane.read_events(TRACE))


def _raw(line_name):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(TRACE).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == line_name:
                    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
    raise LookupError(line_name)


def _window():
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    return e.start_ns, e.start_ns + e.duration_ns


def test_busy_time_is_the_union_of_op_intervals(reduced):
    w0, w1 = _window()
    # an independent count: a 10 ns timeline of the window
    grid = np.zeros(int((w1 - w0) // 10) + 1, bool)
    for s, e, _ in _raw("XLA Ops"):
        s, e = max(s, w0), min(e, w1)
        if e > s:
            grid[int((s - w0) // 10):int((e - w0) // 10)] = True
    assert reduced.n_devices == 1
    assert reduced.window_s == pytest.approx((w1 - w0) * 1e-9)
    # no host sync ends after the plane's last event: read whole
    assert not reduced.span.cut
    assert reduced.traced_s == reduced.window_s
    assert reduced.busy_s == pytest.approx(grid.sum() * 1e-8, rel=0.02)
    assert 0 < reduced.busy_s < reduced.window_s
    idle = sum(s for _, s in reduced.idle_by_label)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s, rel=1e-6)
    # and too short a window for the metrics to be read from it
    assert "under 15.0 s" in reduced.span.unreadable()


def test_program_time_and_runs(reduced):
    w0, w1 = _window()
    runs = [(s, e, n.split("(")[0]) for s, e, n in _raw("XLA Modules")
            if e > w0 and s < w1]
    for name in ("jit__decode_step_fn", "jit__prefill_one_fn"):
        mine = [(s, e) for s, e, n in runs if n == name]
        assert reduced.program_runs[name] == len(mine)
        assert reduced.program_s[name] == pytest.approx(
            sum(min(e, w1) - max(s, w0) for s, e in mine) * 1e-9)
    # 12 decode runs; the first prefill lies before the window's start on
    # the device clock, which runs about 1.2 ms behind the host's here
    assert reduced.program_runs["jit__decode_step_fn"] == 12
    assert reduced.program_runs["jit__prefill_one_fn"] == 2


def test_ops_and_idle_labels(reduced):
    names = [n for n, _ in reduced.top_ops]
    assert "convolution_reduce_fusion" in names
    assert all(" = " not in n for n in names)
    labels = dict(reduced.idle_by_label)
    assert set(labels) <= {"bench.wait", "bench.admit", "bench.step",
                           "(no host annotation)"}
    # the three sleeps of about 5 ms are the largest idle share
    assert reduced.idle_by_label[0][0] == "bench.wait"
    assert labels["bench.wait"] > 0.015


def test_labels_nest():
    labels = [(0, 100, "a"), (10, 20, "b"), (30, 90, "c"), (40, 50, "d")]
    got = xplane._labels_at([5, 15, 25, 45, 60, 95, 150], labels)
    assert got == ["a", "b", "a", "d", "c", "a", "(no host annotation)"]
