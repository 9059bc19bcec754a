"""The harness finds a configuration, a traffic mix, a per-layer metric and
an architecture family by name, from files alone; the dense family's
weights are those it drew before families had modules of their own;
traffic is a function of the seed; a sound reduced run reads correct; and
without an accelerator the measurement path exits non-zero and prints no
result."""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import DATA, ROOT

from bench import harness, weights
from bench.families import dense
from bench.peaks import Peaks

CPU_PEAKS = Peaks(flops=1e12, hbm_bytes_per_s=1e11, hbm_bytes=1e10)


def _tree_hashes(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_mix_and_metric_resolve_by_name(tiny_bench):
    before = _tree_hashes(tiny_bench)
    mix = json.loads((tiny_bench / "traffic" / "tiny-backlog.json").read_text())
    mix["arrivals"] = {"process": "poisson", "rate_rps": 9.0}
    mix["prompt"]["grid"] = [4, 24]
    (tiny_bench / "traffic" / "tiny-new.json").write_text(json.dumps(mix))
    (tiny_bench / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n    return run.steps / run.window_s\n")
    spec_path = tiny_bench.parent / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["workloads"].append({"name": "granite-tiny.new",
                              "config": "granite-tiny",
                              "traffic": "tiny-new", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine", "moves": "tokens_per_s",
                              "workloads": ["granite-tiny.new"]})
    spec_path.write_text(json.dumps(spec))
    after = _tree_hashes(tiny_bench)
    assert {k: v for k, v in after.items() if k in before} == before

    s = harness.load_spec("granite-tiny.new", bench_dir=tiny_bench)
    assert s.mix == mix
    assert "steps_per_s" in [m["name"] for m in s.per_layer]
    gen = s.traffic_module()
    a = gen.generate(mix, 2**40 + 3, 5.0, 500)
    b = gen.generate(mix, 2**40 + 3, 5.0, 500)
    c = gen.generate(mix, 2**40 + 4, 5.0, 500)
    key = lambda reqs: [(r.due_s, r.max_new_tokens, r.prompt.tolist())
                        for r in reqs]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed sends the same sizes at the same times
    sizes = lambda reqs: [(r.due_s, len(r.prompt), r.max_new_tokens)
                          for r in reqs]
    assert sizes(a) == sizes(c)
    # the gaps are drawn, not stratified: blocks of 8 span different times
    spans = [a[i + 7].due_s - a[i].due_s for i in range(0, len(a) - 7, 8)]
    assert len(set(spans)) == len(spans) > 1
    assert {len(r.prompt) for r in a} <= {4, 24}

    result, run = harness.run_cell(s, 11, 2.0, True,
                                   t_process=time.perf_counter(),
                                   require_chip=False, peaks=CPU_PEAKS,
                                   profile_dir=tiny_bench.parent / "prof")
    assert result["metrics"]["steps_per_s"]["value"] > 0
    assert run.steps > 0


def stale_ssm_state(engine):
    """A decode step that hands back every Mamba layer's state unchanged."""
    decode = engine._decode

    def step(params, cache, tokens, pos, live, key):
        nxt, new_cache, new_pos = decode(params, cache, tokens, pos, live, key)
        layers = [dict(new, h=old["h"]) if "h" in old else new
                  for old, new in zip(cache["layers"], new_cache["layers"])]
        return nxt, dict(new_cache, layers=layers), new_pos
    engine._decode = step


def test_new_family_resolves_by_name_and_reads_correct(tiny_bench):
    """A family whose program tree is not the dense one (attention and
    Mamba-1 layers in turn, `data/attn_mamba1`) comes in as new files:
    its module, its reference and counts, and a configuration."""
    before = _tree_hashes(tiny_bench)
    shutil.copytree(DATA / "attn_mamba1", tiny_bench, dirs_exist_ok=True)
    spec_path = tiny_bench.parent / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["workloads"].append({"name": "hybrid-tiny.backlog",
                              "config": "hybrid-tiny",
                              "traffic": "tiny-backlog", "chips": 1,
                              "why": "test"})
    spec_path.write_text(json.dumps(spec))
    after = _tree_hashes(tiny_bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert {"families/attn_mamba1.py", "reference/attn_mamba1.py",
            "reference/attn_mamba1_counts.py",
            "configs/hybrid-tiny.json"} == set(after) - set(before)

    s = harness.load_spec("hybrid-tiny.backlog", bench_dir=tiny_bench)
    family = s.family_module()
    assert pathlib.Path(family.__file__) == (tiny_bench / "families"
                                             / "attn_mamba1.py")
    cfg = family.program_config(s.conf)
    assert [spec.kind for spec in cfg.layer_pattern()] == ["mamba", "attn"]
    # each kind stacked by its own count, not by the number of layers
    table = family.leaf_table(s.conf, cfg.padded_vocab)
    assert table["attn_wq"][1] == table["mamba_in_proj"][1] == 2
    assert s.conf["num_hidden_layers"] == 4

    sound, _ = harness.run_cell(s, 2**35 + 17, 3.0, False,
                                t_process=time.perf_counter(),
                                require_chip=False)
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["logit_gap"]["value"] <= 1e-3
    broken, _ = harness.run_cell(s, 2**35 + 17, 3.0, False,
                                 t_process=time.perf_counter(),
                                 require_chip=False,
                                 engine_hook=stale_ssm_state)
    assert not broken["correct"], broken["checks"]


#: sha256 over name, shape, type and bytes of each leaf, in name order, of
#: the weights drawn for seed 2**40 + 123, as `bench/weights.py` drew them
#: before the dense layout moved to `families/dense.py`
DENSE_WEIGHTS = {
    "granite-tiny":
        "23258aafb88e18a6b444ce0df47a78fac992cfefe5b07e072c2dac8668e90454",
    "starcoder2-tiny":
        "bb12bda2d55faf805fded8cb69708f873c995e0c8ce14a6247ceaf5a2f50bfb1",
}


@pytest.mark.parametrize("name", sorted(DENSE_WEIGHTS))
def test_dense_weights_are_pinned(name):
    conf = json.loads((DATA / f"{name}.json").read_text())
    cfg = dense.program_config(conf)
    w = weights.make_weights(dense.leaf_table(conf, cfg.padded_vocab),
                             conf["torch_dtype"], 2**40 + 123)
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        for part in (k, str(a.shape), str(a.dtype)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DENSE_WEIGHTS[name]


def test_sound_reduced_run_is_correct(tiny_bench):
    s = harness.load_spec("starcoder2-tiny.poisson", bench_dir=tiny_bench)
    result, run = harness.run_cell(s, 2**32 + 9, 3.0, False,
                                   t_process=time.perf_counter(),
                                   require_chip=False)
    assert result["correct"], result
    assert result["checks"]["logit_gap"]["value"] <= 1e-3
    assert set(result["metrics"]) == {"tokens_per_s", "ttft_p50_ms",
                                      "itl_p99_ms", "setup_s"}
    assert list(result)[-1] == "checks"
    # the longest request crossed the 16-wide ring
    assert max(len(g.prompt) + len(g.out_tokens)
               for g in run.gateway.finished) > 16


def test_no_accelerator_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-batch-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
