"""The plain reference against the served path at a reduced size on the
CPU: logits after prefill, and after batched decode with slots at
different positions, past the wrap of a sliding-window ring."""

from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import DATA

from bench import weights as weights_mod
from bench.families import dense as dense_family
from bench.reference import dense


def _engine(conf, seed):
    from repro.models.transformer import param_shape_structs
    from repro.serve import ServeEngine
    cfg = dense_family.program_config(conf)
    w = weights_mod.make_weights(
        dense_family.leaf_table(conf, cfg.padded_vocab), conf["torch_dtype"],
        seed)
    params = dense_family.to_program_tree(w, conf, param_shape_structs(cfg))
    eng = ServeEngine(cfg, params, batch_slots=conf["serving"]["slots"],
                      max_len=conf["serving"]["max_len"], engine="jit")
    return cfg, w, eng


@pytest.mark.parametrize("name", ["granite-tiny", "starcoder2-tiny"])
def test_reference_matches_served_logits(name):
    from repro.models import forward
    from repro.serve import Request
    conf = json.loads((DATA / f"{name}.json").read_text())
    cfg, w, eng = _engine(conf, 2**33 + 5)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, conf["vocab_size"], n, dtype=np.int32)
               for n in (5, 13, 24, 9)]
    reqs = [Request(i, p, 40) for i, p in enumerate(prompts)]
    for r in reqs:
        slot = eng.slot_live.index(False)
        logits, _ = eng._prefill_one(eng.params, eng.cache, r.prompt,
                                     jnp.int32(slot))
        ref = dense.logits(w, conf, r.prompt, [len(r.prompt) - 1],
                           bucket=32)[0]
        np.testing.assert_allclose(np.asarray(logits)[:conf["vocab_size"]],
                                   ref, atol=2e-4, rtol=0)
        assert eng.admit(r)
    for _ in range(20):                  # past the 16-wide ring
        eng.step()
    dec = forward(eng.params, cfg, eng.shd, tokens=eng.last_tok,
                  cache=eng.cache, positions=eng.slot_pos[:, None])[0][:, -1]
    window = conf["sliding_window"] or 0
    wrapped = False
    for slot, r in enumerate(eng.slot_req):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        wrapped |= bool(window) and len(seq) > window
        ref = dense.logits(w, conf, seq, [len(seq) - 1], bucket=64)[0]
        np.testing.assert_allclose(
            np.asarray(dec[slot])[:conf["vocab_size"]], ref, atol=2e-4,
            rtol=0)
    assert wrapped or not window


def test_float8_control_departs_from_reference():
    conf = json.loads((DATA / "granite-tiny.json").read_text())
    w = weights_mod.make_weights(dense_family.leaf_table(conf, 512),
                                 conf["torch_dtype"], 7)
    toks = np.arange(30, dtype=np.int32)
    ref = dense.logits(w, conf, toks, range(30), bucket=32)
    ctl = dense.logits(w, conf, toks, range(30), control=True, bucket=32)
    err = np.abs(ctl - ref).max()
    assert 1e-3 < err < 0.5 * np.abs(ref).max(), err
