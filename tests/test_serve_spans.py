"""The serving loop's own spans and compile counter (`Trace.span`,
`Gateway` and the jit `ServeEngine` with a tracer attached): nesting by
`parent` index, one `queue` span per request, plan-cache consults with a
`solve` only on a miss, `compile` events from `jax.monitoring`, and an
untraced loop that records nothing and opens no profiler annotation."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REDUCED
from repro.dispatch.trace import EVENT_KINDS, Trace
from repro.models import Shardings, init_params
from repro.serve import Gateway, GatewayRequest, Request, ServeEngine

SERVE_KINDS = ("queue", "admit", "gateway_step", "record", "price", "solve",
               "decode_prep", "decode_sync", "decode_record", "prefill_sync")


@pytest.fixture(scope="module")
def params():
    cfg = REDUCED["granite-3-8b"]
    return cfg, init_params(jax.random.PRNGKey(0), cfg, Shardings(None))


def _gateway(params, **kw):
    cfg, p = params
    engine = ServeEngine(cfg, p, batch_slots=2, max_len=32)
    return Gateway(engine, pos_bucket=32, **kw)


def _greq(rid, plen, budget=4):
    return GatewayRequest(rid=rid, prompt=jnp.arange(plen, dtype=jnp.int32)
                          + rid, max_new_tokens=budget)


@pytest.fixture(scope="module")
def served(params):
    """A traced gateway run: three requests through two slots (the third
    queues behind the first two), one refused for its length, and no
    prewarm, so the first consults miss the plan cache."""
    gw = _gateway(params, queue_capacity=8)
    tracer = Trace("spans")
    gw.attach_tracer(tracer)
    try:
        reqs = [_greq(0, 4), _greq(1, 6, budget=5), _greq(2, 4),
                _greq(3, 40)]
        gw.run(reqs)
    finally:
        gw.attach_tracer(None)
    return gw, tracer, reqs


def _kids(tracer, i):
    return [e for e in tracer.events if e.attrs.get("parent") == i]


def test_new_kinds_are_registered(served):
    _, tracer, _ = served
    assert set(SERVE_KINDS) <= set(EVENT_KINDS)
    assert {e.kind for e in tracer.events} <= set(EVENT_KINDS)
    assert {e.kind for e in tracer.events} >= set(SERVE_KINDS)


def test_span_records_parent_and_closes_in_order():
    tracer = Trace("nest")
    with tracer.span("gateway_step", "a", "gateway") as outer:
        with tracer.span("decode_step", "b", "engine", n_live=2) as inner:
            inner.attrs["positions"] = [3, 4]
        with tracer.span("record", "c", "gateway"):
            pass
    with tracer.span("admit", "d", "gateway"):
        pass
    a, b, c, d = tracer.events
    assert "parent" not in a.attrs and "parent" not in d.attrs
    assert b.attrs == {"parent": 0, "n_live": 2, "positions": [3, 4]}
    assert c.attrs == {"parent": 0}
    assert a.t0 <= b.t0 <= b.t1 <= c.t0 <= c.t1 <= a.t1 <= d.t0
    assert outer is a
    # a span closes even when its body raises
    with pytest.raises(ValueError):
        with tracer.span("solve", "e", "planner"):
            raise ValueError
    assert tracer.events[-1].t1 >= tracer.events[-1].t0
    with tracer.span("price", "f", "planner"):
        pass
    assert "parent" not in tracer.events[-1].attrs


def test_decode_steps_nest_inside_gateway_steps(served):
    gw, tracer, _ = served
    ev = tracer.events
    by = {}
    for i, e in enumerate(ev):
        if e.kind in ("decode_prep", "decode_step", "decode_sync",
                      "decode_record"):
            by.setdefault(e.name, {})[e.kind] = i
    steps = [e for e in ev if e.kind == "gateway_step"]
    assert len(steps) == gw.steps == len(by) > 0
    for name, k in by.items():
        assert set(k) == {"decode_prep", "decode_step", "decode_sync",
                          "decode_record"}, name
        step = ev[k["decode_step"]]
        parent = step.attrs["parent"]
        assert ev[parent].kind == "gateway_step"
        assert ev[k["decode_prep"]].attrs["parent"] == parent
        assert ev[k["decode_record"]].attrs["parent"] == parent
        assert ev[k["decode_sync"]].attrs["parent"] == k["decode_step"]
        prep, sync, rec = (ev[k[x]] for x in ("decode_prep", "decode_sync",
                                              "decode_record"))
        assert ev[parent].t0 <= prep.t0 <= prep.t1 <= step.t0 <= sync.t0
        assert sync.t1 <= step.t1 <= rec.t0 <= rec.t1 <= ev[parent].t1
        assert step.attrs["n_live"] == len(step.attrs["positions"]) > 0
    for i, e in enumerate(ev):
        if e.kind == "gateway_step":
            kinds = [c.kind for c in _kids(tracer, i)]
            assert kinds[-1] == "record" and kinds.count("record") == 1


def test_prefill_step_ends_after_its_first_token(served):
    _, tracer, reqs = served
    ev = tracer.events
    pre = [i for i, e in enumerate(ev) if e.kind == "prefill_step"]
    assert [ev[i].name for i in pre] == ["req0", "req1", "req2"]
    for i in pre:
        (sync,) = [c for c in _kids(tracer, i) if c.kind != "compile"]
        assert sync.kind == "prefill_sync" and sync.name == ev[i].name
        assert ev[i].t0 <= sync.t0 <= sync.t1 <= ev[i].t1
        assert ev[ev[i].attrs["parent"]].kind == "admit"


def test_one_queue_span_per_request(served):
    gw, tracer, reqs = served
    queue = tracer.by_kind("queue")
    for g in reqs:
        mine = [e for e in queue if e.attrs["rid"] == g.rid]
        assert len(mine) == 1, g.rid
        assert mine[0].name == f"req{g.rid}" and mine[0].dur_s >= 0
    assert gw.rejected[0].rid == 3
    reasons = {e.attrs["rid"]: e.attrs.get("reason") for e in queue}
    assert reasons == {0: None, 1: None, 2: None, 3: "prompt-too-long"}
    # the third request waited for a slot: its queue span is the longest
    waits = {e.attrs["rid"]: e.dur_s for e in queue}
    assert waits[2] > max(waits[0], waits[1])


def test_admit_rounds_count_what_they_did(served):
    _, tracer, reqs = served
    admits = tracer.by_kind("admit")
    assert sum(e.attrs["admitted"] for e in admits) == 3
    assert all(e.attrs["queued"] >= 0 for e in admits)
    assert admits[0].attrs == {"admitted": 2, "queued": 1}


def test_price_carries_hit_and_solve_only_on_a_miss(served):
    gw, tracer, _ = served
    ev = tracer.events
    prices = [i for i, e in enumerate(ev) if e.kind == "price"]
    assert {ev[i].attrs["phase"] for i in prices} == {"decode", "prefill"}
    hits = [ev[i].attrs["hit"] for i in prices]
    assert True in hits and False in hits
    for i in prices:
        solves = [c for c in _kids(tracer, i) if c.kind == "solve"]
        assert len(solves) == (0 if ev[i].attrs["hit"] else 1)
    assert len(tracer.by_kind("solve")) == hits.count(False) == \
        gw.plans.stats["misses"]


def test_new_prompt_length_compiles_and_warmed_one_does_not(params):
    cfg, p = params
    engine = ServeEngine(cfg, p, batch_slots=1, max_len=32)
    engine.serve([Request(-1, jnp.arange(5, dtype=jnp.int32), 3)])
    warm, new = jnp.arange(5, dtype=jnp.int32) + 1, jnp.arange(7,
                                                             dtype=jnp.int32)
    tracer = Trace("compiles")
    engine.attach_tracer(tracer)
    try:
        engine.serve([Request(0, warm, 3)])
        assert not tracer.by_kind("compile")
        engine.serve([Request(1, new, 3)])
    finally:
        engine.attach_tracer(None)
    compiles = tracer.by_kind("compile")
    assert compiles and all(e.resource == "jax" for e in compiles)
    prefill = [e for e in compiles if e.name == "_prefill_one_fn"]
    assert len(prefill) == 1 and prefill[0].dur_s > 0
    assert prefill[0].attrs["backend_compile_s"] > 0
    (pre,) = [i for i, e in enumerate(tracer.events)
              if e.kind == "prefill_step" and e.name == "req1"]
    assert prefill[0].attrs["parent"] == pre


def test_detach_unregisters_the_compile_listener(params):
    from jax._src import monitoring
    cfg, p = params
    engine = ServeEngine(cfg, p, batch_slots=1, max_len=32)
    before = list(monitoring.get_event_duration_listeners())
    first, second = Trace("a"), Trace("b")
    engine.attach_tracer(first)
    assert len(monitoring.get_event_duration_listeners()) == len(before) + 1
    engine.attach_tracer(second)         # a swap keeps one listener
    listeners = monitoring.get_event_duration_listeners()
    assert len(listeners) == len(before) + 1
    assert second.record_jax_compile in listeners
    engine.attach_tracer(None)
    assert monitoring.get_event_duration_listeners() == before
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3))
    assert not first.by_kind("compile") and not second.by_kind("compile")


class _CountingAnnotation(jax.profiler.TraceAnnotation):
    """A `TraceAnnotation` that counts what it opens and reports a
    profiler session as running."""
    opened: list = []

    def __init__(self, name, **kw):
        type(self).opened.append(name)
        super().__init__(name, **kw)

    @staticmethod
    def is_enabled():
        return True


def test_annotations_open_only_with_a_tracer(params, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = []
    gw = _gateway(params, queue_capacity=4)
    gw.prewarm([4])
    gw.run([_greq(0, 4), _greq(1, 4)])          # untraced
    assert _CountingAnnotation.opened == []
    tracer = Trace("annotated")
    gw.attach_tracer(tracer)
    try:
        gw.run([_greq(2, 4)])
    finally:
        gw.attach_tracer(None)
    opened = set(_CountingAnnotation.opened)
    assert {"serve.gateway_step", "serve.decode_step", "serve.decode_sync",
            "serve.admit", "serve.price", "serve.prefill_step"} <= opened
    spans = [e for e in tracer.events if e.kind not in ("queue", "compile")]
    assert len(_CountingAnnotation.opened) == len(spans)
    n = len(tracer.events)
    _CountingAnnotation.opened = []
    gw.run([_greq(3, 4)])                       # detached again
    assert _CountingAnnotation.opened == [] and len(tracer.events) == n


def test_untraced_step_issues_the_same_device_calls(params, monkeypatch):
    """With no tracer the engine makes the calls it made before spans
    existed: one jitted decode, one `device_get` per step."""
    cfg, p = params
    engine = ServeEngine(cfg, p, batch_slots=2, max_len=32)
    engine.serve([Request(-1, jnp.arange(4, dtype=jnp.int32), 3)])
    calls = {"decode": 0, "get": 0}
    decode = engine._decode

    def counted(*a):
        calls["decode"] += 1
        return decode(*a)
    engine._decode = counted
    real_get = jax.device_get

    def get(x):
        calls["get"] += 1
        return real_get(x)
    monkeypatch.setattr(jax, "device_get", get)
    engine.admit(Request(0, jnp.arange(4, dtype=jnp.int32), 4))
    steps = 1
    while engine.step():
        steps += 1
    assert calls == {"decode": steps, "get": steps}
