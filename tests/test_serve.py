"""Serving engine: continuous batching must be transparent — a request's
greedy output is identical whether it runs alone or batched with others at
skewed positions (exercises the per-row cache-index path)."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import REDUCED
from repro.models import Shardings, init_params
from repro.serve import Request, ServeEngine

SHD = Shardings(None)


@pytest.fixture(scope="module")
def setup():
    cfg = REDUCED["granite-3-8b"]
    params = init_params(jax.random.PRNGKey(0), cfg, SHD)
    return cfg, params


def _prompts(cfg, n, key):
    out = []
    for i in range(n):
        key, k = jax.random.split(key)
        plen = 3 + int(jax.random.randint(k, (), 0, 8))
        out.append(jax.random.randint(k, (plen,), 0, cfg.vocab_size,
                                      dtype=jnp.int32))
    return out


def test_batched_equals_solo(setup):
    cfg, params = setup
    prompts = _prompts(cfg, 5, jax.random.PRNGKey(5))

    solo_outputs = []
    for i, p in enumerate(prompts):
        eng = ServeEngine(cfg, params, batch_slots=1, max_len=64, shd=SHD)
        done = eng.serve([Request(i, p, 6)])
        solo_outputs.append(done[0].out_tokens)

    eng = ServeEngine(cfg, params, batch_slots=3, max_len=64, shd=SHD)
    done = eng.serve([Request(i, p, 6) for i, p in enumerate(prompts)])
    batched = {r.rid: r.out_tokens for r in done}

    for i in range(len(prompts)):
        assert batched[i] == solo_outputs[i], \
            f"req {i}: batched {batched[i]} != solo {solo_outputs[i]}"


def test_all_requests_complete(setup):
    cfg, params = setup
    prompts = _prompts(cfg, 7, jax.random.PRNGKey(9))
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    reqs = [Request(i, p, 4 + i % 3) for i, p in enumerate(prompts)]
    done = eng.serve(reqs)
    assert len(done) == 7
    for r in done:
        assert r.done and len(r.out_tokens) == r.max_new_tokens


# ------------------------------------------------------------------ #
# dispatch-backed decode (ISSUE-2): planner-routed == fused jit
# ------------------------------------------------------------------ #

def _run_16_steps(eng, prompts):
    """A fixed 16-step continuous-batching schedule with arrivals (admit
    whenever a slot is free) and evictions (finished requests leave and
    new ones take their slot mid-run). Returns {rid: tokens} including
    still-inflight requests, so the trace is step-exact."""
    reqs = [Request(i, p, 3 + i % 4) for i, p in enumerate(prompts)]
    pending = list(reqs)
    for _ in range(16):
        while pending and eng.admit(pending[0]):
            pending.pop(0)
        eng.step()
    return {r.rid: (list(r.out_tokens), r.done) for r in reqs}


def test_dispatch_decode_token_identical_to_jit(setup):
    """The PR-2 tentpole gate: routing decode through the offload
    planner's plan (per-stage jit + BankGrid faces) must be a pure
    execution-layer change — token-for-token identical to the fused-jit
    engine over a continuous-batching run with arrivals and evictions.
    Prefill stays fused here (`prefill_engine="jit"`): decode-only
    *bitwise* identity at the default bf16 is only observable when both
    engines decode from bitwise-identical prefilled caches; the dispatch
    prefill path has its own gate below, on the f32 model."""
    cfg, params = setup
    prompts = _prompts(cfg, 8, jax.random.PRNGKey(11))
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_engine": "jit"})
    assert dis_eng.dispatch_plan is not None
    assert dis_eng.dispatch_plan.method == "dag-dp"
    assert dis_eng.prefill_plan is None
    jit_trace = _run_16_steps(jit_eng, prompts)
    dis_trace = _run_16_steps(dis_eng, prompts)
    assert jit_trace == dis_trace


def test_dispatch_decode_forced_hybrid_token_identical(setup_f32, bank_grid):
    """Force the attention stages onto the PIM face (BankGrid local
    phases) regardless of what the planner picks at reduced scale — the
    hybrid execution must still be token-identical. f32: with attention
    weights at their true fan-in, the reduced model's bf16 logits tie
    exactly (three tokens at 0.80078125 for one of these prompts), and
    which tied token argmax takes is decided by sub-ulp rounding the
    per-stage jit does differently."""
    cfg, params = setup_f32
    prompts = _prompts(cfg, 6, jax.random.PRNGKey(13))
    forced = {f"attn{i}": "upmem_2556" for i in range(cfg.n_blocks)}
    forced["embed"] = "upmem_2556"
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, shd=SHD, engine="dispatch",
        dispatch_kwargs={"grid": bank_grid, "force_assignment": forced,
                         "prefill_engine": "jit"})
    assert dis_eng._decode.assignment["attn0"] == "upmem_2556"
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


# ------------------------------------------------------------------ #
# dispatch-backed prefill (ISSUE-3): chunked planner-routed prefill
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def setup_f32():
    """The f32 model for prefill gates: the per-stage prefill is ulp-close
    but not bitwise to the fused forward (stage boundaries change XLA
    fusion), so the token gates run at f32 where the residual is ~1e-7 —
    the same precedent as the two-bank decode gate (DESIGN.md §9)."""
    import dataclasses
    cfg = dataclasses.replace(REDUCED["granite-3-8b"], dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg, SHD)
    return cfg, params


def test_dispatch_prefill_decode_token_identical(setup_f32):
    """The ISSUE-3 tentpole gate, extended over the ISSUE-4 PIPELINED
    path: with BOTH phases planner-routed — chunked prefill over the
    prefill DAG (prompts span 1-3 chunks with ragged tails at chunk=4)
    and decode over the decode DAG — the engine matches the fused-jit
    engine token-for-token over a 16-step continuous-batching run with
    mid-run arrivals and evictions. The multi-chunk prompts here execute
    the executor's interleaved timeline (chunk i+1's qkv issued under
    chunk i's ladder), not a serial chunk loop — asserted below."""
    cfg, params = setup_f32
    prompts = _prompts(cfg, 8, jax.random.PRNGKey(11))
    assert max(int(p.shape[0]) for p in prompts) > 4   # multi-chunk runs
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_chunk": 4})
    assert dis_eng.prefill_plan is not None
    assert dis_eng.prefill_plan.objective == "overlapped"
    assert dis_eng._prefill_step.n_chunks_planned == 4
    # the gated path is pipelined: a 2-chunk prompt's executed node order
    # interleaves chunks (qkv0/c1 before this layer's ladder finishes on
    # chunk 0), unlike the old chunk-major loop
    two_chunk = dis_eng._prefill_step._executor_for([4, 4])
    flat = [n for _, nodes in two_chunk.executed_order() for n in nodes]
    assert flat.index("qkv0/c1") < flat.index("mlp0/c0")
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


def test_dispatch_prefill_forced_pim_token_identical(setup_f32, bank_grid):
    """Force every prefill chunk's embed + attention onto the PIM face
    (sequence-sharded BankGrid local phases) regardless of the planner's
    pick — the hybrid chunked prefill must stay token-identical."""
    cfg, params = setup_f32
    prompts = _prompts(cfg, 6, jax.random.PRNGKey(13))
    forced = {}
    for c in range(4):
        forced[f"embed/c{c}"] = "upmem_2556"
        for i in range(cfg.n_blocks):
            forced[f"attn{i}/c{c}"] = "upmem_2556"
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, shd=SHD, engine="dispatch",
        dispatch_kwargs={"grid": bank_grid, "prefill_chunk": 4,
                         "prefill_force_assignment": forced})
    assert dis_eng._prefill_step.assignment["attn0/c0"] == "upmem_2556"
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


def test_dispatch_prefill_plan_routes_chunks(setup_f32):
    """The prefill plan covers every planned chunk's stage ladder, longer
    prompts clamp onto the last planned chunk, and the ragged tail reuses
    the chunk grid."""
    cfg, params = setup_f32
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                      engine="dispatch",
                      dispatch_kwargs={"prefill_chunk": 4})
    step = eng._prefill_step
    for c in range(step.n_chunks_planned):
        for i in range(cfg.n_blocks):
            for stage in ("qkv", "attn", "o", "mlp"):
                assert f"{stage}{i}/c{c}" in step.assignment
    assert "head" in step.assignment
    assert step.chunk_splits(11) == [4, 4, 3]
    assert step.chunk_splits(4) == [4]


def test_steps_route_through_unified_executor(setup_f32):
    """The ISSUE-4 acceptance gate: neither dispatch step owns a private
    stage-execution loop — both are adapters over
    `dispatch.executor.PlanExecutor`, and the executed launch-group order
    is exactly the planner schedule's group order."""
    from repro.dispatch.executor import PlanExecutor
    from repro.serve.dispatch_engine import (DispatchDecodeStep,
                                             DispatchPrefillStep)
    for cls in (DispatchDecodeStep, DispatchPrefillStep):
        for legacy in ("_run", "_stages"):
            assert not hasattr(cls, legacy), \
                f"{cls.__name__}.{legacy}: private stage machinery is back"
    cfg, params = setup_f32
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                      engine="dispatch",
                      dispatch_kwargs={"prefill_chunk": 4})
    for step in (eng._decode, eng._prefill_step):
        # instance-level check too: per-step face caches must not come
        # back beside the shared FaceCache/PlanExecutor path
        for legacy in ("_run", "_stages", "_host", "_pim"):
            assert legacy not in vars(step), \
                f"{type(step).__name__}.{legacy}: private stage machinery"
        assert isinstance(step.executor, PlanExecutor)
        order = step.executor.executed_order()
        # groups are maximal same-device runs of the DAG's topo order
        flat = [n for _, nodes in order for n in nodes]
        assert flat == step.executor.graph.topo_order()
        for dev, nodes in order:
            assert all(step.executor.assignment[n] == dev for n in nodes)
        for a, b in zip(order, order[1:]):
            assert a[0] != b[0], "adjacent groups on one device"
    # ragged/over-horizon prompts clamp onto the planned placement
    pre = eng._prefill_step
    devs = pre.devices_for(4 * pre.n_chunks_planned + 6)   # 2 extra chunks
    last = pre.n_chunks_planned - 1
    for i in range(cfg.n_blocks):
        assert devs[f"qkv{i}/c{last + 2}"] == \
            pre.assignment[f"qkv{i}/c{last}"]


def test_dispatch_three_layer_hybrid_token_identical():
    """Regression (executor env freeing): every layer's qkv re-reads
    embed's sin/cos although the DAG only edges embed->qkv0/o0 — with
    >= 3 layers and attention forced onto PIM (multiple launch groups),
    a freeing contract that follows graph edges alone would drop embed
    after layer 0 and KeyError at qkv2. Both phases must stay
    token-identical to the fused engine at depth 3."""
    import dataclasses
    cfg = dataclasses.replace(REDUCED["granite-3-8b"], n_layers=3,
                              dtype="float32")
    params = init_params_for(cfg)
    prompts = _prompts(cfg, 5, jax.random.PRNGKey(17))
    forced = {f"attn{i}": "upmem_2556" for i in range(cfg.n_blocks)}
    pforced = {}
    for c in range(4):
        for i in range(cfg.n_blocks):
            pforced[f"attn{i}/c{c}"] = "upmem_2556"
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, shd=SHD, engine="dispatch",
        dispatch_kwargs={"force_assignment": forced, "prefill_chunk": 4,
                         "prefill_force_assignment": pforced})
    assert len(dis_eng._decode.executor.executed_order()) > 3
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


# ------------------------------------------------------------------ #
# dispatch-backed MoE serving (ISSUE-5): routed experts as an exchange
# phase — token-identity gates for the planner-routed MoE ladder
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def setup_moe():
    """The f32 mixtral-reduced model: routed MoE (4 experts top-2,
    sliding-window attention), no shared experts — the dispatch engine's
    MoE scope. f32 for the same reason as the prefill gates (per-stage
    jit changes XLA fusion; DESIGN.md §9)."""
    import dataclasses
    from repro.configs import REDUCED
    cfg = dataclasses.replace(REDUCED["mixtral-8x7b"], dtype="float32")
    return cfg, init_params_for(cfg)


def test_dispatch_moe_decode_token_identical_to_jit(setup_moe):
    """The ISSUE-5 e2e gate, mirroring the dense decode gate: routing MoE
    decode through the planner's plan (router -> token exchange -> expert
    FFNs -> combine exchange per layer) must be token-for-token identical
    to the fused-jit engine over a 16-step continuous-batching run with
    arrivals and evictions, on the f32 model. Prefill stays fused here
    (`prefill_engine="jit"`), the dense gate's precedent — chunked MoE
    prefill has per-chunk capacity semantics (gates below)."""
    cfg, params = setup_moe
    prompts = _prompts(cfg, 8, jax.random.PRNGKey(11))
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_engine": "jit"})
    assert dis_eng.dispatch_plan is not None
    assert dis_eng.dispatch_plan.method == "dag-dp"
    # the decode DAG carries the routed ladder and its exchange edges
    dag = dis_eng._decode.dag
    assert "router0" in dag.nodes and "expert0" in dag.nodes
    assert ("router0", "expert0") in dag.exchange_edges
    assert ("expert0", "combine0") in dag.exchange_edges
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


def test_dispatch_moe_forced_expert_pim_token_identical(setup_moe,
                                                        bank_grid):
    """Force every layer's router + expert (and attention) onto the PIM
    face regardless of the planner's pick: the router->expert edge
    becomes an intra-PIM exchange the executor must relay through the
    host (gather/scatter), with the expert FFN sharded over the grid's
    expert axis — still token-identical to the fused engine."""
    cfg, params = setup_moe
    prompts = _prompts(cfg, 6, jax.random.PRNGKey(13))
    forced = {}
    for i in range(cfg.n_blocks):
        forced[f"attn{i}"] = "upmem_2556"
        forced[f"router{i}"] = "upmem_2556"
        forced[f"expert{i}"] = "upmem_2556"
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, shd=SHD, engine="dispatch",
        dispatch_kwargs={"grid": bank_grid, "force_assignment": forced,
                         "prefill_engine": "jit"})
    # the intra-PIM exchange is registered for the executor's host relay
    assert sorted(dis_eng._decode.executor._exchange_in) == \
        sorted(f"expert{i}" for i in range(cfg.n_blocks))
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


def test_dispatch_moe_expert_sharded_decode_token_identical(setup_moe):
    """ISSUE-9 rank-sharded expert faces: decode with `expert_shards=2`
    builds the expert-parallel DAG (shard nodes `expert{i}@r{j}`, each
    owning E/R experts), pins shard j on rank j's device, and must stay
    token-for-token identical to the fused engine — the combine
    reassembles the rank shards' outputs along the expert axis, which is
    exact because experts compute independently. The forced per-rank
    placement makes the executor stage each shard's boundary transfers
    per rank device, the executable twin of the schedule's per-rank
    channels."""
    cfg, params = setup_moe
    prompts = _prompts(cfg, 6, jax.random.PRNGKey(17))
    forced = {}
    for i in range(cfg.n_blocks):
        forced[f"expert{i}@r0"] = "upmem_2556"
        forced[f"expert{i}@r1"] = "upmem_2556:1"
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, shd=SHD, engine="dispatch",
        dispatch_kwargs={"expert_shards": 2,
                         "devices": ("xeon", "upmem_2556", "upmem_2556:1"),
                         "force_assignment": forced,
                         "prefill_engine": "jit"})
    dag = dis_eng._decode.dag
    # the sharded ladder: per-shard exchange edges, no fused expert node
    assert "expert0@r0" in dag.nodes and "expert0@r1" in dag.nodes
    assert "expert0" not in dag.nodes
    assert ("router0", "expert0@r1") in dag.exchange_edges
    assert ("expert0@r1", "combine0") in dag.exchange_edges
    assert dis_eng._decode.assignment["expert0@r1"] == "upmem_2556:1"
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


def test_dispatch_moe_single_chunk_prefill_token_identical(setup_moe):
    """Dispatch MoE prefill in ONE chunk covers the whole prompt, so the
    per-chunk expert capacity equals the fused whole-prompt capacity and
    the full dispatch path (prefill AND decode planner-routed) matches
    the fused engine token-for-token. Multi-chunk MoE prefill drops
    overflow per chunk by design and is gated for bank-count identity
    instead (the slow multibank gate)."""
    cfg, params = setup_moe
    prompts = _prompts(cfg, 8, jax.random.PRNGKey(11))
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_chunk": 48})
    assert dis_eng.prefill_plan is not None
    pre_dag = dis_eng._prefill_step.dag
    assert any(n.startswith("router") for n in pre_dag.nodes)
    assert pre_dag.exchange_edges
    assert _run_16_steps(jit_eng, prompts) == _run_16_steps(dis_eng, prompts)


@pytest.mark.slow
def test_dispatch_moe_multibank_matches_single_bank():
    """ISSUE-5 satellite: full MoE dispatch serving (planner-routed
    chunked prefill AND decode, experts forced onto the PIM face) with
    the EXPERT axis sharded over TWO banks must be token-identical to the
    single-bank run — each bank owns its experts' weights and dispatch
    rows, and the host gather/scatter exchange is what re-distributes
    tokens between the slot/chunk sharding and the expert sharding.
    Subprocess per the dry-run isolation rule; f32 model."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parent.parent
    code = (
        "import dataclasses, jax, jax.numpy as jnp\n"
        "from repro.configs import REDUCED\n"
        "from repro.core.bank_parallel import BankGrid, make_bank_mesh\n"
        "from repro.models import Shardings, init_params\n"
        "from repro.serve import Request, ServeEngine\n"
        "shd = Shardings(None)\n"
        "cfg = dataclasses.replace(REDUCED['mixtral-8x7b'],\n"
        "                          dtype='float32')\n"
        "params = init_params(jax.random.PRNGKey(0), cfg, shd)\n"
        "key = jax.random.PRNGKey(5)\n"
        "prompts = []\n"
        "for _ in range(6):\n"
        "    key, k = jax.random.split(key)\n"
        "    plen = 4 + int(jax.random.randint(k, (), 0, 8))\n"
        "    prompts.append(jax.random.randint(k, (plen,), 0,\n"
        "                   cfg.vocab_size, dtype=jnp.int32))\n"
        "forced, pforced = {}, {}\n"
        "for i in range(cfg.n_blocks):\n"
        "    forced[f'attn{i}'] = 'upmem_2556'\n"
        "    forced[f'router{i}'] = 'upmem_2556'\n"
        "    forced[f'expert{i}'] = 'upmem_2556'\n"
        "    for c in range(4):\n"
        "        pforced[f'expert{i}/c{c}'] = 'upmem_2556'\n"
        "outs = {}\n"
        "for n_banks in (1, 2):\n"
        "    grid = BankGrid(make_bank_mesh(n_banks))\n"
        "    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48,\n"
        "        shd=shd, engine='dispatch', dispatch_kwargs={\n"
        "        'grid': grid, 'force_assignment': forced,\n"
        "        'prefill_chunk': 4,\n"
        "        'prefill_force_assignment': pforced})\n"
        "    assert eng._decode.executor._exchange_in, 'no exchanges'\n"
        "    done = eng.serve([Request(i, p, 5)\n"
        "                      for i, p in enumerate(prompts)])\n"
        "    outs[n_banks] = {r.rid: r.out_tokens for r in done}\n"
        "assert outs[1] == outs[2], outs\n"
        "print('MOE_MULTIBANK_OK')\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=f"{root / 'src'}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MOE_MULTIBANK_OK" in out.stdout


@pytest.mark.slow
def test_dispatch_serving_multibank_matches_single_bank():
    """ISSUE-4 satellite: full dispatch serving (planner-routed prefill
    AND decode) with batch slots sharded over TWO banks must be
    token-identical to the single-bank run — the executor's PIM faces
    shard slots (decode, axis 0) and chunk token rows (prefill, axis 1)
    over however many banks the grid has. Subprocess per the dry-run
    isolation rule; f32 model (bf16 can flip a near-tie argmax across
    bank-shard tilings, DESIGN.md §9)."""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parent.parent
    code = (
        "import dataclasses, jax, jax.numpy as jnp\n"
        "from repro.configs import REDUCED\n"
        "from repro.core.bank_parallel import BankGrid, make_bank_mesh\n"
        "from repro.models import Shardings, init_params\n"
        "from repro.serve import Request, ServeEngine\n"
        "shd = Shardings(None)\n"
        "cfg = dataclasses.replace(REDUCED['granite-3-8b'], dtype='float32')\n"
        "params = init_params(jax.random.PRNGKey(0), cfg, shd)\n"
        "key = jax.random.PRNGKey(5)\n"
        "prompts = []\n"
        "for _ in range(6):\n"
        "    key, k = jax.random.split(key)\n"
        "    plen = 4 + int(jax.random.randint(k, (), 0, 8))\n"
        "    prompts.append(jax.random.randint(k, (plen,), 0,\n"
        "                   cfg.vocab_size, dtype=jnp.int32))\n"
        "forced = {f'attn{i}': 'upmem_2556' for i in range(cfg.n_blocks)}\n"
        "forced['embed'] = 'upmem_2556'\n"
        "pforced = {}\n"
        "for c in range(4):\n"
        "    pforced[f'embed/c{c}'] = 'upmem_2556'\n"
        "    for i in range(cfg.n_blocks):\n"
        "        pforced[f'attn{i}/c{c}'] = 'upmem_2556'\n"
        "outs = {}\n"
        "for n_banks in (1, 2):\n"
        "    grid = BankGrid(make_bank_mesh(n_banks))\n"
        "    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48,\n"
        "        shd=shd, engine='dispatch', dispatch_kwargs={\n"
        "        'grid': grid, 'force_assignment': forced,\n"
        "        'prefill_chunk': 4,\n"
        "        'prefill_force_assignment': pforced})\n"
        "    pim_groups = [d for d, _ in\n"
        "                  eng._decode.executor.executed_order()\n"
        "                  if d.startswith('upmem')]\n"
        "    assert pim_groups, 'no PIM launch groups to shard'\n"
        "    done = eng.serve([Request(i, p, 5)\n"
        "                      for i, p in enumerate(prompts)])\n"
        "    outs[n_banks] = {r.rid: r.out_tokens for r in done}\n"
        "assert outs[1] == outs[2], outs\n"
        "print('MULTIBANK_SERVE_OK')\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=f"{root / 'src'}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIBANK_SERVE_OK" in out.stdout


@pytest.mark.slow
def test_dispatch_decode_two_banks_token_identical():
    """Real multi-bank sharding (subprocess, dry-run isolation rule):
    slots sharded 2-ways over banks, attention forced onto the BankGrid
    face, f32 model — token-identical to the fused-jit engine. (bf16 can
    flip a near-tie argmax across bank-shard tilings — an XLA rounding
    artifact, so the cross-bank gate runs the f32 model.)"""
    import os
    import pathlib
    import subprocess
    import sys
    root = pathlib.Path(__file__).resolve().parent.parent
    code = (
        "import dataclasses, jax, jax.numpy as jnp\n"
        "from repro.configs import REDUCED\n"
        "from repro.core.bank_parallel import BankGrid, make_bank_mesh\n"
        "from repro.models import Shardings, init_params\n"
        "from repro.serve import Request, ServeEngine\n"
        "shd = Shardings(None)\n"
        "cfg = dataclasses.replace(REDUCED['granite-3-8b'], dtype='float32')\n"
        "params = init_params(jax.random.PRNGKey(0), cfg, shd)\n"
        "grid = BankGrid(make_bank_mesh())\n"
        "assert grid.n_banks == 2\n"
        "key = jax.random.PRNGKey(3)\n"
        "prompts = []\n"
        "for _ in range(6):\n"
        "    key, k = jax.random.split(key)\n"
        "    plen = 3 + int(jax.random.randint(k, (), 0, 6))\n"
        "    prompts.append(jax.random.randint(k, (plen,), 0,\n"
        "                   cfg.vocab_size, dtype=jnp.int32))\n"
        "forced = {f'attn{i}': 'upmem_2556' for i in range(cfg.n_blocks)}\n"
        "forced['embed'] = 'upmem_2556'\n"
        "outs = {}\n"
        "for name, kw in (('jit', {}), ('dispatch', dict(\n"
        "        engine='dispatch', dispatch_kwargs={'grid': grid,\n"
        "        'force_assignment': forced,\n"
        "        'prefill_engine': 'jit'}))):\n"
        "    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48,\n"
        "                      shd=shd, **kw)\n"
        "    done = eng.serve([Request(i, p, 5)\n"
        "                      for i, p in enumerate(prompts)])\n"
        "    outs[name] = {r.rid: r.out_tokens for r in done}\n"
        "assert outs['jit'] == outs['dispatch'], outs\n"
        "print('OK')\n")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=f"{root / 'src'}")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dispatch_engine_rejects_unsupported_configs(setup):
    cfg, params = setup
    from repro.configs import REDUCED
    rwkv = REDUCED["rwkv6-3b"]
    with pytest.raises(ValueError, match="decoders"):
        ServeEngine(rwkv, init_params_for(rwkv), batch_slots=1, max_len=16,
                    shd=SHD, engine="dispatch")
    # routed MoE is supported (mixtral); shared-expert MoE is not
    shared = REDUCED["qwen2-moe-a2.7b"]
    with pytest.raises(ValueError, match="shared experts"):
        ServeEngine(shared, init_params_for(shared), batch_slots=1,
                    max_len=16, shd=SHD, engine="dispatch")
    with pytest.raises(ValueError, match="engine must be"):
        ServeEngine(cfg, params, batch_slots=1, max_len=16, shd=SHD,
                    engine="nope")


def init_params_for(cfg):
    from repro.models import init_params
    return init_params(jax.random.PRNGKey(0), cfg, SHD)


def test_decode_step_shapes(setup):
    cfg, params = setup
    from repro.models import init_cache
    from repro.serve import make_decode_step, make_prefill_step
    b, w = 2, 32
    cache = init_cache(cfg, b, w, SHD)
    prefill = make_prefill_step(cfg, SHD)
    decode = make_decode_step(cfg, SHD)
    toks = jnp.ones((b, 8), jnp.int32)
    last, cache = prefill(params, cache, {"tokens": toks})
    assert last.shape == (b, cfg.padded_vocab)
    lg, cache = decode(params, cache, jnp.ones((b, 1), jnp.int32))
    assert lg.shape == (b, cfg.padded_vocab)
    assert int(cache["index"]) == 9


def _outer_scan_lengths(jaxpr):
    """Lengths of the scans not nested inside another scan."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _outer_scan_lengths(sub)
    return found


def test_cached_forward_scans_one_block():
    """The engine's prefill and decode scan the stacked layers one block
    per iteration whatever `remat_group` is (a group's weight slice would
    be copied out of the stack each iteration); the uncached forward
    still scans groups."""
    import dataclasses
    import functools
    import types
    from repro.models import forward, init_cache
    cfg = dataclasses.replace(REDUCED["granite-3-8b"], n_layers=4,
                              remat_group=2)
    assert cfg.n_blocks == 4
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg,
                                                SHD))
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 32, SHD))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    # the engine's own steps, bound to a stand-in so no cache is allocated
    engine = types.SimpleNamespace(cfg=cfg, shd=SHD, temperature=0.0,
                                   max_len=32)
    decode = functools.partial(ServeEngine._decode_step_fn, engine)
    prefill = functools.partial(ServeEngine._prefill_one_fn, engine)
    live = jax.ShapeDtypeStruct((2,), jnp.bool_)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jp = jax.make_jaxpr(decode)(params, cache, i32(2, 1), i32(2), live, key)
    assert _outer_scan_lengths(jp.jaxpr) == [cfg.n_blocks]
    jp = jax.make_jaxpr(prefill)(params, cache, i32(8), i32())
    assert _outer_scan_lengths(jp.jaxpr) == [cfg.n_blocks]
    jp = jax.make_jaxpr(lambda p, t: forward(p, cfg, SHD, tokens=t))(
        params, i32(2, 8))
    assert _outer_scan_lengths(jp.jaxpr) == [cfg.n_blocks // 2]


# ------------------------------------------------------------------ #
# ISSUE-7 serve-loop bugfix regressions
# ------------------------------------------------------------------ #

def test_budget_one_yields_exactly_one_token_fused(setup):
    """Before the fix, admit() never checked max_new_tokens on the first
    sampled token: a budget-1 request entered decode and generated a
    second token. Now it finishes at admit and the slot stays free."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    req = Request(0, jnp.asarray([3, 1, 4], jnp.int32), max_new_tokens=1)
    assert eng.admit(req)
    assert req.done and len(req.out_tokens) == 1
    assert eng.n_free == 2 and eng.step() == 0
    # and through the serve loop, mixed with multi-token requests
    reqs = [Request(1, jnp.asarray([2, 7], jnp.int32), 1),
            Request(2, jnp.asarray([1, 8, 2], jnp.int32), 4)]
    done = eng.serve(reqs)
    assert sorted(len(r.out_tokens) for r in done) == [1, 4]


def test_budget_one_yields_exactly_one_token_dispatch(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD,
                      engine="dispatch",
                      dispatch_kwargs={"prefill_chunk": 4})
    req = Request(0, jnp.asarray([3, 1, 4, 1, 5], jnp.int32),
                  max_new_tokens=1)
    assert eng.admit(req)
    assert req.done and len(req.out_tokens) == 1
    assert eng.n_free == 2


def test_eos_on_first_token_finishes_at_admit(setup):
    """EOS can land on the FIRST sampled token; before the fix the done
    check only ran inside step(), so the request decoded one token past
    its EOS. Greedy sampling makes the first token reproducible: observe
    it, then replay the same prompt with eos_id set to it."""
    cfg, params = setup
    prompt = jnp.asarray([5, 9, 2, 6], jnp.int32)
    probe = Request(0, prompt, max_new_tokens=1)
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=48, shd=SHD)
    assert eng.admit(probe)
    first = probe.out_tokens[0]

    eng = ServeEngine(cfg, params, batch_slots=1, max_len=48, shd=SHD,
                      eos_id=first)
    req = Request(1, prompt, max_new_tokens=8)
    assert eng.admit(req)
    assert req.done and req.out_tokens == [first]
    assert eng.n_free == 1


def test_admit_validates_prompt_and_budget(setup):
    """admit() used to silently accept prompts with len >= max_len,
    overflowing the scatter into the batched cache."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=16, shd=SHD)
    with pytest.raises(ValueError, match="does not fit max_len"):
        eng.admit(Request(0, jnp.ones((16,), jnp.int32), 4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.admit(Request(1, jnp.ones((4,), jnp.int32), 0))
    assert eng.n_free == 1        # neither invalid request held a slot
    # a prompt of max_len - 1 still fits (one generated token)
    ok = Request(2, jnp.ones((15,), jnp.int32), 4)
    assert eng.admit(ok) and not eng.slot_req[0] is None


def test_step_syncs_device_once(setup, monkeypatch):
    """step() used to do a per-slot int(slot_pos[slot]) sync in the
    finish loop plus a second device_get in the tracer branch; both now
    reuse ONE hoisted device_get per step — with or without a tracer."""
    from repro.dispatch import trace as dtrace
    cfg, params = setup
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, shd=SHD)
    eng.admit(Request(0, jnp.asarray([1, 2, 3], jnp.int32), 6))
    eng.admit(Request(1, jnp.asarray([4, 5], jnp.int32), 6))
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.append(1), real(x))[1])
    eng.step()
    assert len(calls) == 1
    calls.clear()
    eng.attach_tracer(dtrace.Trace("sync-count"))
    eng.step()
    assert len(calls) == 1


def test_engine_prefill_splits_hook(setup):
    """The gateway keys prefill pricing by the engine's chunk grid: one
    fused chunk on the jit path, the dispatch prefill step's splits on
    the dispatch path."""
    cfg, params = setup
    jit_eng = ServeEngine(cfg, params, batch_slots=1, max_len=48, shd=SHD)
    assert jit_eng.prefill_splits(11) == [11]
    dis_eng = ServeEngine(cfg, params, batch_slots=1, max_len=48, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_chunk": 4})
    assert dis_eng.prefill_splits(11) == [4, 4, 3]
    assert dis_eng.prefill_splits(4) == [4]


# ------------------------------------------------------------------ #
# windowed serving (ISSUE-10): ring-cache decode + banded prefill
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def setup_swa():
    """A mistral-style sliding-window config at f32: starcoder2-reduced
    (dense, window 16, attention bias) — at max_len 32 the engine's KV
    cache is a RING of width 16, so decode slots wrap and slot index !=
    absolute position (the ISSUE-10 bug surface)."""
    import dataclasses
    cfg = dataclasses.replace(REDUCED["starcoder2-7b"], dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg, SHD)
    return cfg, params


def _run_16_steps_wrapping(eng, prompts):
    """The 16-step continuous-batching schedule with budgets big enough
    that positions cross the ring width mid-decode."""
    reqs = [Request(i, p, 8) for i, p in enumerate(prompts)]
    pending = list(reqs)
    for _ in range(16):
        while pending and eng.admit(pending[0]):
            pending.pop(0)
        eng.step()
    return {r.rid: (list(r.out_tokens), r.done) for r in reqs}


def test_windowed_dispatch_decode_token_identical(setup_swa):
    """The ISSUE-10 serving gate: windowed dispatch decode against the
    ring cache is token-identical to the fused engine over a 16-step
    continuous-batching run whose positions wrap the ring (prompts of
    12-14 tokens + 8 generated cross width 16)."""
    from repro.models import cache as cache_lib
    cfg, params = setup_swa
    assert cache_lib.cache_width(cfg, 32) == 16    # ring, not full
    key = jax.random.PRNGKey(17)
    prompts = [jax.random.randint(jax.random.fold_in(key, i),
                                  (12 + i % 3,), 0, cfg.vocab_size,
                                  dtype=jnp.int32) for i in range(4)]
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_engine": "jit"})
    from repro.serve.dispatch_engine import dims_for_config
    assert dims_for_config(cfg, 2, 32).window == cfg.sliding_window
    jit_trace = _run_16_steps_wrapping(jit_eng, prompts)
    assert any(len(p) + len(toks) > 16
               for p, (toks, _) in zip(prompts, jit_trace.values()))
    assert jit_trace == _run_16_steps_wrapping(dis_eng, prompts)


def test_windowed_banded_prefill_token_identical(setup_swa):
    """Banded dispatch prefill: prompts LONGER than the window execute
    the banded KV prefix (chunk 5 of a 22-token prompt drops chunk 0,
    matching the DAG's dropped edges) and stay token-identical to the
    fused engine — fully-masked keys contribute exactly zero at f32, so
    dropping them is exact, not approximate."""
    from repro.dispatch import workloads
    cfg, params = setup_swa
    key = jax.random.PRNGKey(23)
    plens = [22, 20, 9, 18]
    prompts = [jax.random.randint(jax.random.fold_in(key, i), (plens[i],),
                                  0, cfg.vocab_size, dtype=jnp.int32)
               for i in range(4)]
    jit_eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, shd=SHD)
    dis_eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, shd=SHD,
                          engine="dispatch",
                          dispatch_kwargs={"prefill_chunk": 4})
    step = dis_eng._prefill_step
    lf = workloads.prefill_live_from(step.chunk_splits(22),
                                     cfg.sliding_window)
    assert lf[-1] == 1                     # banding actually engages
    reqs = [Request(i, p, 3) for i, p in enumerate(prompts)]

    def run(eng):
        rs = [Request(r.rid, prompts[r.rid], 3) for r in reqs]
        pending = list(rs)
        for _ in range(12):
            while pending and eng.admit(pending[0]):
                pending.pop(0)
            eng.step()
        return {r.rid: (list(r.out_tokens), r.done) for r in rs}

    assert run(jit_eng) == run(dis_eng)
