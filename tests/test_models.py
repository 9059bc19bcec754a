"""Model-layer equivalence tests — the numerics that make the zoo correct.

The decode-vs-full-forward equivalence is the strongest integration
invariant: prefill + N greedy decode steps must reproduce the logits of one
full forward over the same tokens (per-family: dense/SWA, SSM, enc-dec)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# compile-bound: the whole arch zoo retraces here; tier-1 skips by default
pytestmark = pytest.mark.slow

from repro.configs import REDUCED
from repro.models import Shardings, forward, init_cache, init_params
from repro.models import layers as L
from repro.models.config import ModelConfig

SHD = Shardings(None)


# --------------------------------------------------------------------- #
# attention building blocks
# --------------------------------------------------------------------- #

def test_flash_equals_plain():
    cfg = REDUCED["llama3-405b"]
    b, s, h, hd = 2, 64, 4, 16
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, 2, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, 2, hd))
    import dataclasses
    cfg8 = dataclasses.replace(cfg, q_chunk=8, kv_chunk=16)
    from repro.models.transformer import _plain_attention
    got = L.flash_attention(q, k, v, cfg8, SHD, causal=True)
    want = _plain_attention(q, k, v, cfg8, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_sliding_window():
    import dataclasses
    cfg = dataclasses.replace(REDUCED["mixtral-8x7b"], sliding_window=16,
                              q_chunk=8, kv_chunk=8)
    b, s, h, hd = 1, 64, 2, 8
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (b, s, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, hd))
    from repro.models.transformer import _plain_attention
    got = L.flash_attention(q, k, v, cfg, SHD, causal=True)
    want = _plain_attention(q, k, v, cfg, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_rope_rotation_preserves_norm():
    cfg = REDUCED["llama3-405b"]
    pos = jnp.arange(8)[None].repeat(2, 0)
    sin, cos = L.rope_sincos(pos, cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, cfg.hd))
    y = L.apply_rope(x, sin, cos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)


def test_mrope_reduces_to_rope_for_text():
    """When t==h==w (text tokens), M-RoPE must equal 1-D RoPE on the first
    2/3... actually on ALL sections (same positions per stream)."""
    cfg = REDUCED["qwen2-vl-72b"]
    b, s = 2, 8
    pos = jnp.arange(s)[None].repeat(b, 0)
    mpos = jnp.broadcast_to(pos[None], (3, b, s))
    sin_m, cos_m = L.rope_sincos(mpos, cfg)
    import dataclasses
    cfg1 = dataclasses.replace(cfg, rope="rope")
    sin_1, cos_1 = L.rope_sincos(pos, cfg1)
    np.testing.assert_allclose(np.asarray(sin_m), np.asarray(sin_1),
                               rtol=1e-6)


# --------------------------------------------------------------------- #
# decode == full forward (per family)
# --------------------------------------------------------------------- #

DECODE_EQUIV_ARCHS = ["llama3-405b", "starcoder2-7b", "granite-3-8b",
                      "rwkv6-3b", "deepseek-coder-33b"]


@pytest.mark.parametrize("name", DECODE_EQUIV_ARCHS)
def test_decode_matches_full_forward(name):
    cfg = REDUCED[name]
    b, s_pre, s_tot = 2, 8, 14
    key = jax.random.PRNGKey(11)
    toks = jax.random.randint(key, (b, s_tot), 0, cfg.vocab_size)
    params = init_params(key, cfg, SHD)

    full_logits, _, _ = forward(params, cfg, SHD, tokens=toks)

    cache = init_cache(cfg, b, 32, SHD)
    _, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :s_pre],
                          cache=cache)
    dec = []
    for t in range(s_pre, s_tot):
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, t:t + 1],
                               cache=cache)
        dec.append(lg[:, 0])
    got = jnp.stack(dec, axis=1)                 # (b, s_tot-s_pre, V)
    want = full_logits[:, s_pre:s_tot]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_decode_matches_full_forward_jamba(monkeypatch):
    """Hybrid (mamba+attn+moe): states must carry across prefill/decode.
    Capacity-dropping legitimately differs between prefill widths (standard
    GShard semantics), so make capacity non-binding — then decode must be
    EXACT (it was 0.82-correlated before isolating the drops)."""
    monkeypatch.setattr(L, "CAPACITY_FACTOR", 8.0)
    cfg = REDUCED["jamba-1.5-large-398b"]
    b, s_pre, s_tot = 2, 8, 12
    key = jax.random.PRNGKey(12)
    toks = jax.random.randint(key, (b, s_tot), 0, cfg.vocab_size)
    params = init_params(key, cfg, SHD)
    full_logits, _, _ = forward(params, cfg, SHD, tokens=toks)
    cache = init_cache(cfg, b, 32, SHD)
    _, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :s_pre],
                          cache=cache)
    dec = []
    for t in range(s_pre, s_tot):
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, t:t + 1],
                               cache=cache)
        dec.append(lg[:, 0])
    got = jnp.stack(dec, axis=1)
    want = full_logits[:, s_pre:s_tot]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_ring_cache_sliding_window_decode():
    """Mixtral-reduced (window 16): decoding past the window must match a
    full forward (which masks beyond the window) despite ring overwrite."""
    import dataclasses
    cfg = dataclasses.replace(REDUCED["mixtral-8x7b"], n_experts=0, top_k=0)
    b, s_tot = 1, 40   # window is 16 << 40
    key = jax.random.PRNGKey(13)
    toks = jax.random.randint(key, (b, s_tot), 0, cfg.vocab_size)
    params = init_params(key, cfg, SHD)
    full_logits, _, _ = forward(params, cfg, SHD, tokens=toks)
    cache = init_cache(cfg, b, s_tot, SHD)  # ring width = window = 16
    _, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :8], cache=cache)
    dec = []
    for t in range(8, s_tot):
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, t:t + 1],
                               cache=cache)
        dec.append(lg[:, 0])
    got = jnp.stack(dec, axis=1)
    want = full_logits[:, 8:s_tot]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_whisper_cross_attention_cache():
    cfg = REDUCED["whisper-tiny"]
    b = 2
    key = jax.random.PRNGKey(14)
    enc = jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model),
                            jnp.float32)
    toks = jax.random.randint(key, (b, 10), 0, cfg.vocab_size)
    params = init_params(key, cfg, SHD)
    full_logits, _, _ = forward(params, cfg, SHD, tokens=toks,
                                encoder_embeds=enc)
    cache = init_cache(cfg, b, 16, SHD)
    _, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :6],
                          encoder_embeds=enc, cache=cache)
    dec = []
    for t in range(6, 10):
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, t:t + 1],
                               cache=cache)   # no encoder: uses cached K/V
        dec.append(lg[:, 0])
    got = jnp.stack(dec, axis=1)
    want = full_logits[:, 6:10]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------------- #
# vocab padding
# --------------------------------------------------------------------- #

def test_vocab_padding_masked():
    cfg = REDUCED["granite-3-8b"]
    assert cfg.padded_vocab > cfg.vocab_size        # 515 -> 640
    params = init_params(jax.random.PRNGKey(0), cfg, SHD)
    toks = jnp.zeros((1, 4), jnp.int32)
    logits, _, _ = forward(params, cfg, SHD, tokens=toks)
    pads = np.asarray(logits, np.float32)[..., cfg.vocab_size:]
    assert (pads <= -1e29).all()


def test_moe_aux_loss_bounds():
    cfg = REDUCED["mixtral-8x7b"]
    params = init_params(jax.random.PRNGKey(0), cfg, SHD)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    _, _, aux = forward(params, cfg, SHD, tokens=toks)
    # perfectly balanced -> 1.0 per moe layer; capacity blow-ups explode it
    n_moe = cfg.n_layers
    assert 0.5 * n_moe < float(aux) < 4.0 * n_moe


# --------------------------------------------------------------------- #
# §Perf optimizations: numerical-equivalence regressions
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("mode", ["train", "serve"])
def test_remat_group_equivalence_f32(mode):
    """remat_group is a pure memory/recompute trade: EXACT in f32
    (EXPERIMENTS.md §Perf llama3 iteration). `train`: forward and grads;
    `serve`: prefill and three decode steps through the cache."""
    import dataclasses
    from repro.models import lm_loss
    base = dataclasses.replace(REDUCED["granite-3-8b"], n_layers=4,
                               dtype="float32")
    g2 = dataclasses.replace(base, remat_group=2)
    key = jax.random.PRNGKey(0)
    params = init_params(key, base, SHD)
    toks = jax.random.randint(key, (2, 16), 0, base.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (2, 16), 0,
                                base.vocab_size)

    def loss(cfg):
        def f(p):
            lg, _, aux = forward(p, cfg, SHD, tokens=toks)
            return lm_loss(lg, labels, aux)
        return jax.value_and_grad(f)(params)

    def served(cfg):
        cache = init_cache(cfg, 2, 32, SHD)
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :13],
                               cache=cache)
        out = [lg]
        for t in range(13, 16):
            lg, cache, _ = forward(params, cfg, SHD,
                                   tokens=toks[:, t:t + 1], cache=cache)
            out.append(lg)
        return out

    if mode == "train":
        l1, g1 = loss(base)
        l2, gg = loss(g2)
        assert float(l1) == float(l2)
    else:
        g1, gg = served(base), served(g2)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(gg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_rwkv_chunked_equals_per_token():
    """The chunked-parallel wkv (MXU reformulation, §Perf rwkv iteration)
    must match the per-token recurrence (decode path) exactly."""
    import dataclasses
    from repro.models import init_cache
    cfg = dataclasses.replace(REDUCED["rwkv6-3b"], dtype="float32")
    key = jax.random.PRNGKey(3)
    params = init_params(key, cfg, SHD)
    b, s = 2, 24                       # 24 % WKV_CHUNK(8) == 0
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    full, _, _ = forward(params, cfg, SHD, tokens=toks)   # chunked path
    cache = init_cache(cfg, b, 32, SHD)
    _, cache, _ = forward(params, cfg, SHD, tokens=toks[:, :8],
                          cache=cache)
    dec = []
    for t in range(8, s):              # per-token recurrence path
        lg, cache, _ = forward(params, cfg, SHD, tokens=toks[:, t:t + 1],
                               cache=cache)
        dec.append(lg[:, 0])
    got = jnp.stack(dec, 1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full[:, 8:s]),
                               rtol=1e-4, atol=1e-4)
