"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

The cell's entry in `BENCHMARK.json` names its configuration and traffic
mix; both are files found by name under this directory, as are the metric
readers (`metrics/<name>.py`) and, by the configuration's `family`, the
program config and weight layout (`families/<family>.py`), the reference
(`reference/<family>.py`) and its counts. Nothing here names a cell or a
family.

The window drives `Gateway` over `ServeEngine(engine="jit")` through the
gateway's public calls, in the order `Gateway.run` makes them: requests are
submitted when due, admitted under the stall budget, and decoded in batched
steps until `--seconds` have passed, with work still queued.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Callable

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache, at one fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: seconds a run waits past the window for the first token of every
#: request that was due in it, at most
DRAIN_LIMIT_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold dots and dashes)."""
    name = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    """A cell and everything it names, resolved from files."""
    cell: dict
    conf: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: pathlib.Path

    def traffic_module(self):
        return load_module(self.bench_dir / "traffic" / f"{self.mix['kind']}.py")

    def metric_module(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py")

    def family_module(self):
        return load_module(self.bench_dir / "families"
                           / f"{self.conf['family']}.py")

    def reference_module(self):
        return load_module(self.bench_dir / "reference" / f"{self.conf['family']}.py")

    def counts_module(self):
        return load_module(self.bench_dir / "reference"
                           / f"{self.conf['family']}_counts.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(workload: str, bench_dir: pathlib.Path = BENCH,
              benchmark_json: pathlib.Path | None = None) -> Spec:
    """Resolve a cell of `BENCHMARK.json` to its files."""
    path = benchmark_json or bench_dir.parent / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {path}")
    cell = cells[workload]
    conf = json.loads((bench_dir / "configs" / f"{cell['config']}.json").read_text())
    mix = json.loads((bench_dir / "traffic" / f"{cell['traffic']}.json").read_text())
    return Spec(cell, conf, mix,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)],
                bench_dir)


def check_devices(chips: int):
    """The devices the run uses; `NoChip` unless JAX holds enough
    accelerators."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """Every program, however quick to compile, goes to the checkout's
    fixed cache directory, so only a checkout's first run compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Run:
    """What one run recorded; the metric readers take it. Times are
    `time.perf_counter` seconds unless named otherwise."""
    spec: Spec
    seed: int
    seconds: float
    setup_s: float
    setup_parts: dict
    window0: float
    window1: float
    requests: list                     # GatewayRequest, in due order
    due_in_window: list                # GatewayRequest due inside the window
    gateway: Any
    plan_stats0: dict
    plan_stats1: dict
    steps: int                         # decode steps in the window
    wait_s: float = 0.0                # window time spent waiting for arrivals
    spans: list | None = None          # engine spans (trace runs)
    span_offset: float = 0.0           # perf_counter of the tracer's origin
    reduced: Any = None                # xplane.Reduced (trace runs)
    trace_events: Any = None           # xplane.Events (trace runs)
    peaks: Any = None
    memory_peak_bytes: int = 0
    drain_end: float = 0.0

    @property
    def window_s(self) -> float:
        return self.window1 - self.window0

    def token_times_in_window(self) -> list[float]:
        return [t for g in self.requests for t in g.token_times
                if self.window0 <= t <= self.window1]

    def spans_in_window(self, kind: str) -> list:
        """Engine spans of `kind` that lie inside the window, each as
        (start, end, attrs) in perf_counter seconds."""
        out = []
        for ev in self.spans or ():
            if ev.kind != kind:
                continue
            s, e = self.span_offset + ev.t0, self.span_offset + ev.t1
            if s >= self.window0 and e <= self.window1:
                out.append((s, e, ev.attrs))
        return out

    def spans_in_trace(self, kind: str) -> list:
        """`spans_in_window` of those that end inside the part of the
        window that the device trace holds."""
        end = self.window0 + self.reduced.traced_s
        return [sp for sp in self.spans_in_window(kind) if sp[1] <= end]

    def counts(self):
        return self.spec.counts_module()


def _annotate(obj, attr: str, name: str, sync: bool = False) -> None:
    """Wrap a method of `obj` in a host annotation; with `sync`, the
    wrapper also waits for the device to finish what the call started."""
    import jax
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            out = fn(*a, **k)
            return jax.block_until_ready(out) if sync else out
    setattr(obj, attr, wrapped)


def _annotation(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_chip: bool = True,
             engine_hook: Callable | None = None,
             control: bool = False, profile_dir: pathlib.Path | None = None,
             peaks=None, check: bool = True):
    """Run one cell and return (result dict, Run). `engine_hook(engine)`
    may alter the engine before the window (tests plant faults with it)."""
    import jax

    chips = int(spec.cell.get("chips", 1))
    devices = check_devices(chips) if require_chip else jax.devices()
    if require_chip:
        enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.models.transformer import param_shape_structs
    from repro.serve import Gateway, GatewayRequest, Request, ServeEngine

    sys.path.insert(0, str(spec.bench_dir.parent))
    weights_mod = load_module(spec.bench_dir / "weights.py")
    family = spec.family_module()
    conf, mix = spec.conf, spec.mix
    serving = conf["serving"]
    traffic = spec.traffic_module()
    cfg = family.program_config(conf)
    parts = {}

    t = time.perf_counter()
    weights = weights_mod.make_weights(
        family.leaf_table(conf, cfg.padded_vocab), conf["torch_dtype"], seed)
    params = family.to_program_tree(weights, conf, param_shape_structs(cfg))
    parts["init_s"] = time.perf_counter() - t
    log(f"init: {sum(a.size for a in jax.tree.leaves(weights)) / 1e9:.3f}B "
        f"parameters made on the device in {parts['init_s']:.2f} s")

    engine = ServeEngine(cfg, params, batch_slots=serving["slots"],
                         max_len=serving["max_len"], engine="jit", seed=seed)
    gateway = Gateway(engine, queue_capacity=mix["queue_capacity"],
                      pos_bucket=serving["pos_bucket"])
    lens = traffic.prompt_lengths(mix)
    t = time.perf_counter()
    warm = gateway.prewarm(lens)
    parts["prewarm_s"] = time.perf_counter() - t
    log(f"prewarm: {warm['misses']} plan signatures in {parts['prewarm_s']:.2f} s")

    # every prefill length of the mix and the decode step, through the
    # engine's own calls, so that nothing compiles inside the window
    t = time.perf_counter()
    engine.serve([Request(-1 - i, np.zeros((n,), np.int32), 2)
                  for i, n in enumerate(lens)])
    parts["compile_s"] = time.perf_counter() - t
    log(f"warm-up: prefill at {lens} tokens and the decode step in "
        f"{parts['compile_s']:.2f} s")
    if engine_hook is not None:
        engine_hook(engine)

    arrivals = traffic.generate(mix, seed, seconds, conf["vocab_size"])
    reqs = [GatewayRequest(rid=i, prompt=a.prompt,
                           max_new_tokens=a.max_new_tokens, priority=1)
            for i, a in enumerate(arrivals)]

    tracer = None
    if trace:
        from repro.dispatch.trace import Trace
        tracer = Trace("bench")
        gateway.attach_tracer(tracer)
        span_offset = time.perf_counter() - tracer.now()
        for obj, attr, name in ((gateway, "submit", "bench.submit"),
                                (gateway, "admit_pending", "bench.admit"),
                                (gateway, "step", "bench.step"),
                                (gateway.plans, "get_or_plan", "bench.planner"),
                                (engine, "_decode", "bench.engine.decode")):
            _annotate(obj, attr, name)
        # the engine closes its `prefill_step` span when the prefill is
        # dispatched and syncs just after, on the sampled token; waiting
        # inside the call makes the span hold the prefill itself, as
        # `decode_step` holds its step
        _annotate(engine, "_prefill_one", "bench.engine.prefill", sync=True)
        profile_dir = profile_dir or ROOT / ".bench_profile"
        shutil.rmtree(profile_dir, ignore_errors=True)
        jax.profiler.start_trace(str(profile_dir))

    plan0 = dict(gateway.plans.stats)
    steps0 = gateway.steps
    clock = time.perf_counter
    with _annotation(trace, "bench.window"):
        w0 = clock()
        setup_s = w0 - t_process
        w1_target = w0 + seconds
        for g, a in zip(reqs, arrivals):
            g.arrival_s = w0 + a.due_s
        pending = list(reversed(reqs))
        late = []
        wait_s = 0.0
        while True:
            now = clock()
            if now >= w1_target:
                break
            while pending and pending[-1].arrival_s <= now:
                g = pending.pop()
                late.append(now - g.arrival_s)
                gateway.submit(g)
            if not gateway.running and len(gateway.queue) == 0:
                nxt = pending[-1].arrival_s if pending else w1_target
                with _annotation(trace, "bench.wait"):
                    t = clock()
                    time.sleep(max(0.0, min(nxt, w1_target) - t))
                    wait_s += clock() - t
                continue
            gateway.admit_pending()
            if engine.n_slots - engine.n_free > 0:
                gateway.step()
        w1 = clock()
    if trace:
        jax.profiler.stop_trace()
    steps = gateway.steps - steps0
    plan1 = dict(gateway.plans.stats)
    due = [g for g in reqs if g.arrival_s < w1_target]
    for g in reversed(pending):            # due before the close, not yet sent
        if g.arrival_s < w1_target:
            gateway.submit(g)
    if mix.get("drain_s"):
        limit = clock() + min(float(mix["drain_s"]), DRAIN_LIMIT_S)
        while clock() < limit and any(not g.token_times and g.state != "rejected"
                                      for g in due):
            gateway.admit_pending()
            if engine.n_slots - engine.n_free > 0:
                gateway.step()
    drain_end = clock()
    if late:
        log(f"generator: {len(late)} requests sent, lateness median "
            f"{np.median(late) * 1e3:.1f} ms, max {max(late) * 1e3:.1f} ms")
    log(f"window: {w1 - w0:.3f} s, {steps} decode steps, "
        f"{len(gateway.finished)} requests finished, "
        f"{len(gateway.running)} running, {len(gateway.queue)} queued")

    mem = devices[0].memory_stats() or {}
    run = Run(spec=spec, seed=seed, seconds=seconds, setup_s=setup_s,
              setup_parts=parts, window0=w0, window1=w1, requests=reqs,
              due_in_window=due, gateway=gateway, plan_stats0=plan0,
              plan_stats1=plan1, steps=steps, drain_end=drain_end,
              wait_s=wait_s,
              memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)))
    if trace:
        run.spans = list(tracer.events)
        run.span_offset = span_offset

    # free the program's state before the reference runs
    finished = [g for g in gateway.finished]
    engine.cache = None
    engine.params = None
    del engine, params
    gc.collect()

    checks, check_ok, reasons = (
        check_outputs(spec, weights, finished, seed, control=control)
        if check else ({}, False, ["not checked"]))
    del weights
    gc.collect()

    failed = sum(1 for g in due if g.state == "rejected" or not g.token_times)
    if mix["arrivals"]["process"] == "backlog":
        failed = sum(1 for g in due if g.state == "rejected")
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": check_ok and failed == 0, "attempted": len(due),
              "failed": failed}
    if failed:
        reasons.append(f"{failed} requests due in the window failed")

    if trace:
        xplane = load_module(spec.bench_dir / "xplane.py")
        t = time.perf_counter()
        run.trace_events = xplane.read_events(
            xplane.find_xplane(str(profile_dir)))
        run.reduced = r = xplane.reduce_trace(run.trace_events)
        log(f"trace: the device plane holds {r.traced_s!r} s of the "
            f"{r.window_s!r} s window ({'cut' if r.span.cut else 'whole'}"
            f"), {r.span.decode_runs} decode runs; "
            f"{r.span.unreadable() or 'runs and syncs paired'}; read in "
            f"{time.perf_counter() - t:.1f} s")
        run.peaks = peaks or load_module(
            spec.bench_dir / "peaks.py").peaks_for(d.device_kind)
        # both over the part of the window that the trace holds, so that
        # their ratio is the idle share there; `traced_s` names that part
        device["busy_s"] = r.busy_s
        device["window_s"] = r.traced_s
        device["traced_s"] = r.traced_s
        metrics = read_metrics(spec.per_layer, run)
        run.trace_events = None
    else:
        metrics = read_metrics(spec.end_to_end, run)
    result["metrics"] = metrics
    result["device"] = device
    if trace:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.reduced.top_ops],
            "idle_gaps": [[n, s] for n, s in run.reduced.idle_by_label]}
    result["reasons"] = reasons
    result["checks"] = checks
    return result, run


def read_metrics(entries: list[dict], run: Run) -> dict:
    out = {}
    for m in entries:
        value = run.spec.metric_module(m["name"]).read(run)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def pick_sample(finished: list, target_tokens: int, min_requests: int,
                seed: int) -> list:
    """Requests to check: the longest finished one (prompt and served
    tokens), then others drawn from the seed until the sample holds
    `target_tokens` served tokens and `min_requests` requests."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 2])
    longest = max(finished, key=lambda g: (len(g.prompt) + len(g.out_tokens),
                                           g.rid))
    rest = [g for g in finished if g is not longest]
    sample = [longest]
    n = len(longest.out_tokens)
    for i in rng.permutation(len(rest)):
        if n >= target_tokens and len(sample) >= min_requests:
            break
        sample.append(rest[i])
        n += len(rest[i].out_tokens)
    return sample


def check_outputs(spec: Spec, weights, finished: list, seed: int, *,
                  control: bool = False):
    """Compare the served tokens of a seeded sample of finished requests
    with the reference. The number compared is the widest gap by which a
    served token's reference logit lies below the reference's best at its
    position. With `control`, the reference in float8 takes the program's
    place: at each position of the same prompts and served tokens, the
    token it puts first is judged in the same way, against the same
    limit. Returns (checks, ok, reasons)."""
    ref = spec.reference_module()
    check = spec.conf["check"]
    limit = check["logit_gap_limit"]
    vocab = spec.conf["vocab_size"]
    bucket = spec.conf["check"].get("bucket", 1024)
    t = time.perf_counter()
    sample = pick_sample(finished, check["tokens"], check["requests"], seed)
    gap = 0.0
    program_gap = 0.0
    n_tokens = 0
    finite = True
    for g in sample:
        prompt = np.asarray(g.prompt, np.int32)
        out = np.asarray(g.out_tokens, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        rows = np.arange(len(prompt) - 1, len(seq))
        if out.min() < 0 or out.max() >= vocab:
            finite = False
            continue
        lg = ref.logits(weights, spec.conf, seq, rows, bucket=bucket)
        finite &= bool(np.isfinite(lg).all())
        best = lg.max(axis=1)
        program_gap = max(program_gap,
                          float((best - lg[np.arange(len(out)), out]).max()))
        if control:
            out = ref.logits(weights, spec.conf, seq, rows, control=True,
                             bucket=bucket).argmax(axis=1)
        gap = max(gap, float((best - lg[np.arange(len(out)), out]).max()))
        n_tokens += len(out)
    log(f"reference: {len(sample)} requests, {n_tokens} served tokens "
        f"checked in {time.perf_counter() - t:.2f} s")
    if control:
        log(f"control run: the float8 reference's logit_gap {gap!r} is "
            f"compared; the program's own reads {program_gap!r}")
    reasons = []
    if not finite:
        reasons.append("a served token is outside the vocabulary or a "
                       "reference logit is not finite")
    if n_tokens < check["tokens"] or len(sample) < check["requests"]:
        reasons.append(f"only {len(sample)} requests with {n_tokens} served "
                       f"tokens finished; {check['requests']} requests and "
                       f"{check['tokens']} tokens are compared")
    if limit is None:
        reasons.append("no limit is set for logit_gap")
    elif not gap <= limit:
        reasons.append(f"logit_gap {gap!r} exceeds its limit {limit}")
    checks = {"requests_checked": {"value": len(sample),
                                   "limit": check["requests"]},
              "tokens_checked": {"value": n_tokens, "limit": check["tokens"]},
              "logit_gap": {"value": gap, "limit": limit}}
    return checks, not reasons, reasons
