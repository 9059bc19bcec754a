"""A reduced run with the timed path broken underneath it comes out not
correct, once for each fault a served cell can have: a decode step that
returns its cache unchanged, half of the batch's tokens left out, and a
token altered where it is produced. The harness's look for a chip is
skipped; everything else is the run as the benchmark makes it."""

from __future__ import annotations

import time

import pytest

from bench import harness


def stale_cache(engine):
    decode = engine._decode

    def step(params, cache, tokens, pos, live, key):
        nxt, _, new_pos = decode(params, cache, tokens, pos, live, key)
        return nxt, cache, new_pos
    engine._decode = step


def half_batch(engine):
    decode = engine._decode

    def step(params, cache, tokens, pos, live, key):
        nxt, new_cache, new_pos = decode(params, cache, tokens, pos, live, key)
        half = tokens.shape[0] // 2
        return nxt.at[half:].set(tokens[half:]), new_cache, new_pos
    engine._decode = step


def altered_token(engine):
    decode = engine._decode
    vocab = engine.cfg.vocab_size

    def step(params, cache, tokens, pos, live, key):
        nxt, new_cache, new_pos = decode(params, cache, tokens, pos, live, key)
        return nxt.at[0].set((nxt[0] + 1) % vocab), new_cache, new_pos
    engine._decode = step


@pytest.mark.parametrize("fault", [stale_cache, half_batch, altered_token])
def test_fault_reads_not_correct(tiny_bench, fault):
    # the backlog keeps every slot busy, so each fault reaches a sampled
    # request
    s = harness.load_spec("granite-tiny.backlog", bench_dir=tiny_bench)
    result, _ = harness.run_cell(s, 2**31 + 77, 3.0, False,
                                 t_process=time.perf_counter(),
                                 require_chip=False, engine_hook=fault)
    assert not result["correct"], result["checks"]
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
