"""Model assembly: params, forward (train / prefill / decode), loss.

The decoder stack scans over repeats of the config's layer pattern (blocks);
heterogeneous stacks (jamba) unroll the pattern inside the scan body. The
uncached forward scans groups of `remat_group` blocks, each group
rematerialized; a cached forward scans one block at a time (see
`stack_forward`). Cache tensors ride the scan as xs/ys so decode state
stays stacked and shardable.

Forward modes:
  * cache=None, S tokens      -> training / eval forward
  * cache given, S>1          -> prefill (writes KV, returns logits+cache)
  * cache given, S==1         -> decode step
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from . import cache as cache_lib
from . import layers as L
from .config import LayerSpec, ModelConfig
from .mamba import mamba_defs, mamba_forward
from .rwkv import (rwkv_channel_mix, rwkv_defs, rwkv_time_mix)
from .sharding import (ParamDef, Shardings, is_def, stack_defs, tree_specs,
                       tree_shape_structs)


# --------------------------------------------------------------------- #
# parameter definitions
# --------------------------------------------------------------------- #

def layer_defs(cfg: ModelConfig, spec: LayerSpec, name: str) -> dict:
    d: dict[str, Any] = {"ln1": L.norm_defs(cfg, f"{name}.ln1")}
    if spec.kind == "attn":
        d["attn"] = L.attn_defs(cfg, f"{name}.attn")
        if spec.cross_attn:
            d["ln_cross"] = L.norm_defs(cfg, f"{name}.ln_cross")
            d["cross"] = L.attn_defs(cfg, f"{name}.cross")
    elif spec.kind == "mamba":
        d["mamba"] = mamba_defs(cfg, f"{name}.mamba")
    elif spec.kind == "rwkv":
        d["rwkv"] = rwkv_defs(cfg, f"{name}.rwkv")
        d["ln2"] = L.norm_defs(cfg, f"{name}.ln2")
        return d
    if spec.mlp != "none":
        d["ln2"] = L.norm_defs(cfg, f"{name}.ln2")
        d["mlp"] = (L.moe_defs(cfg, f"{name}.moe") if spec.mlp == "moe"
                    else L.mlp_defs(cfg, f"{name}.mlp"))
    return d


def param_defs(cfg: ModelConfig) -> dict:
    v, dm = cfg.padded_vocab, cfg.d_model
    defs: dict[str, Any] = {
        # embedding: D sharded over tp so lookup is a local gather
        "embed": ParamDef((v, dm), (None, "tp"), "embed", "normal"),
        "final_norm": L.norm_defs(cfg, "final_norm"),
    }
    if not cfg.tie_embeddings:
        # unembedding: vocab-parallel logits
        defs["unembed"] = ParamDef((v, dm), ("vocab", "fsdp"), "unembed")
    pattern = cfg.layer_pattern()
    defs["layers"] = [
        stack_defs(layer_defs(cfg, spec, f"l{i}"), cfg.n_blocks)
        for i, spec in enumerate(pattern)]
    if cfg.encoder_layers:
        enc_spec = LayerSpec("attn", "dense", cross_attn=False)
        defs["encoder"] = {
            "layers": stack_defs(layer_defs(cfg, enc_spec, "enc"),
                                 cfg.encoder_layers),
            "final_norm": L.norm_defs(cfg, "enc.final_norm"),
        }
    return defs


def init_scale(d: ParamDef) -> float:
    """Standard deviation of a random leaf: 0.02 for "small", else
    1/sqrt(fan_in), so each output of a projection starts at unit scale."""
    if d.init == "small":
        return 0.02
    fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2 else d.shape[-1])
    return 1.0 / math.sqrt(fan_in)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _init_leaf(key, shape, dtype, init, scale, sharding):
    """One parameter leaf, built on the device in `dtype`: the f32 normal
    draw is a fused temporary, never a full-size buffer of its own."""
    if init == "zeros":
        arr = jnp.zeros(shape, dtype)
    elif init == "ones":
        arr = jnp.ones(shape, dtype)
    else:
        arr = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    if sharding is not None:
        arr = jax.lax.with_sharding_constraint(arr, sharding)
    return arr


def init_params(rng, cfg: ModelConfig, shd: Shardings | None = None):
    defs = param_defs(cfg)
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(rng, len(leaves))

    def mk(d: ParamDef, key):
        sharding = shd.named(d.shape, d.kinds, d.name) if shd else None
        return _init_leaf(key, d.shape, jnp.dtype(d.dtype or cfg.dtype),
                          d.init, init_scale(d), sharding)

    return jax.tree.unflatten(treedef, [mk(d, k) for d, k in zip(leaves, keys)])


def param_shape_structs(cfg: ModelConfig):
    return tree_shape_structs(param_defs(cfg), cfg.dtype)


def param_specs(cfg: ModelConfig, shd: Shardings):
    return tree_specs(shd, param_defs(cfg))


# --------------------------------------------------------------------- #
# attention sub-layer with all cache modes
# --------------------------------------------------------------------- #

def _attention(x, p, cfg: ModelConfig, shd: Shardings, rope, kv_cache,
               index, width):
    """Returns (attn_out, new_kv_cache)."""
    b, s, _ = x.shape
    sin, cos = rope
    decoding = kv_cache is not None and s == 1
    q, k, v = L._qkv(x, p, cfg, shd, rope_sin=sin, rope_cos=cos,
                     heads_tp=not decoding)

    if kv_cache is None:  # training: full self-attention over s
        if s >= 2048 and s % cfg.q_chunk == 0 and s % cfg.kv_chunk == 0:
            o = L.flash_attention(q, k, v, cfg, shd, causal=True)
        else:
            o = _plain_attention(q, k, v, cfg, causal=True)
        return L.attn_out(o, p, x.dtype, shd), None

    if s > 1:  # prefill (ring caches keep the trailing window)
        new_kv = cache_lib.write_prefill(kv_cache, k, v)
        if s >= 2048 and s % cfg.q_chunk == 0 and s % cfg.kv_chunk == 0:
            o = L.flash_attention(q, k, v, cfg, shd, causal=True)
        else:
            o = _plain_attention(q, k, v, cfg, causal=True)
        return L.attn_out(o, p, x.dtype, shd), new_kv

    # decode
    new_kv = cache_lib.write_decode(kv_cache, k, v, index, width)
    positions = cache_lib.slot_positions(index + 1, width)
    o = L.cached_attention(q, new_kv["k"], new_kv["v"], positions, index,
                           cfg, shd)
    return L.attn_out(o, p, x.dtype, shd), new_kv


def _cross_attention(x, p, cfg: ModelConfig, shd: Shardings, cross_cache,
                     encoder_out):
    """Whisper-style cross attention. Prefill computes and caches encoder
    K/V; decode reuses them."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if encoder_out is not None:
        k = jnp.einsum("bsd,dhk->bshk", encoder_out, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", encoder_out, p["wv"].astype(x.dtype))
        if "bk" in p:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        new_cache = (None if cross_cache is None
                     else cache_lib.write_prefill(cross_cache, k, v))
    else:
        assert cross_cache is not None
        k, v = cross_cache["k"].astype(x.dtype), cross_cache["v"].astype(x.dtype)
        new_cache = cross_cache
    o = _plain_attention(q, k, v, cfg, causal=False)
    return L.attn_out(o, p, x.dtype, shd), new_cache


def _plain_attention(q, k, v, cfg: ModelConfig, causal: bool,
                     q_offset: int = 0):
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = jnp.arange(skv)
        mask = q_pos[:, None] >= k_pos[None, :]
        if cfg.sliding_window:
            mask &= q_pos[:, None] - k_pos[None, :] < cfg.sliding_window
        s = jnp.where(mask, s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


# --------------------------------------------------------------------- #
# block and stack
# --------------------------------------------------------------------- #

def block_forward(x, spec: LayerSpec, p, cfg: ModelConfig, shd: Shardings,
                  rope, cache_slice, index, width, encoder_out):
    """One pattern position. Returns (x, new_cache_slice, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache_slice
    h = L.apply_norm(x, p["ln1"], cfg)
    if spec.kind == "attn":
        kv = None if cache_slice is None else {
            "k": cache_slice["k"], "v": cache_slice["v"]}
        o, new_kv = _attention(h, p["attn"], cfg, shd, rope, kv, index, width)
        x = x + o
        if cache_slice is not None:
            new_cache = dict(cache_slice, **new_kv)
        if spec.cross_attn:
            h = L.apply_norm(x, p["ln_cross"], cfg)
            cc = None if cache_slice is None else cache_slice.get("cross")
            o, new_cc = _cross_attention(h, p["cross"], cfg, shd, cc,
                                         encoder_out)
            x = x + o
            if cache_slice is not None and new_cc is not None:
                new_cache = dict(new_cache, cross=new_cc)
    elif spec.kind == "mamba":
        state = cache_slice if cache_slice is not None else None
        o, new_state = mamba_forward(h, p["mamba"], cfg, shd, state)
        x = x + o
        if cache_slice is not None:
            new_cache = new_state
    elif spec.kind == "rwkv":
        state = cache_slice if cache_slice is not None else {
            "wkv": jnp.zeros((x.shape[0], cfg.n_rwkv_heads,
                              cfg.rwkv_head_size, cfg.rwkv_head_size),
                             jnp.float32),
            "shift_tm": jnp.zeros((x.shape[0], 1, cfg.d_model), x.dtype),
            "shift_cm": jnp.zeros((x.shape[0], 1, cfg.d_model), x.dtype),
        }
        o, tm_state = rwkv_time_mix(h, p["rwkv"], cfg, shd, state)
        x = x + o
        h2 = L.apply_norm(x, p["ln2"], cfg)
        o2, cm_state = rwkv_channel_mix(h2, p["rwkv"], cfg, shd, state)
        x = x + o2
        if cache_slice is not None:
            new_cache = dict(state, **tm_state, **cm_state)
        return x, new_cache, aux

    if spec.mlp != "none":
        h = L.apply_norm(x, p["ln2"], cfg)
        if spec.mlp == "moe":
            o, aux = L.moe_forward(h, p["mlp"], cfg, shd)
        else:
            o = L.mlp_forward(h, p["mlp"], cfg, shd)
        x = x + o
    x = shd.act(x, "batch", "seq", None)
    return x, new_cache, aux


def stack_forward(x, params, cfg: ModelConfig, shd: Shardings, rope,
                  cache_layers, index, width, encoder_out):
    """Scan the stack one block per iteration, or, uncached, over groups of
    `remat_group` blocks.

    The uncached forward (training, eval) unrolls the pattern and the
    group inside the rematerialized body, so its backward saves
    activations only at group boundaries (n_blocks/remat_group stacked
    residuals instead of n_blocks — the §Perf memory-term lever).

    A cached forward (prefill, decode) is never differentiated, so the
    grouping saves nothing there, and it costs: a group's slice of a
    stacked leaf has one user per block, so XLA copies the whole group of
    weights (and cache) out of the stack on every iteration instead of
    fusing each block's slice into its matmul. So it scans the stacked
    leaves as they are stored, one block at a time."""
    pattern = cfg.layer_pattern()
    have_cache = cache_layers is not None
    g = 1 if have_cache else max(cfg.remat_group, 1)
    if cfg.n_blocks % g != 0:
        g = 1
    n_steps = cfg.n_blocks // g

    def body(carry, xs):
        xc, aux_acc = carry
        layer_ps, cache_slices = xs
        new_slices = []
        for j in range(g):
            lp = (jax.tree.map(lambda l: l[j], layer_ps) if g > 1
                  else layer_ps)
            for i, spec in enumerate(pattern):
                sl = cache_slices[i] if have_cache else None
                xc, new_sl, aux = block_forward(
                    xc, spec, lp[i], cfg, shd, rope, sl, index, width,
                    encoder_out)
                aux_acc = aux_acc + aux
                new_slices.append(new_sl)
        return (xc, aux_acc), (tuple(new_slices) if have_cache else 0)

    if cfg.remat:
        body = jax.checkpoint(body)

    layer_xs = params["layers"]
    if g > 1:
        layer_xs = jax.tree.map(
            lambda l: l.reshape((n_steps, g) + l.shape[1:]), layer_xs)
    cache_xs = tuple(cache_layers) if have_cache else _zeros_xs(cfg, n_steps)
    (x, aux), new_cache = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (layer_xs, cache_xs))
    return x, (list(new_cache) if have_cache else None), aux


def _zeros_xs(cfg: ModelConfig, n_steps: int | None = None):
    # placeholder xs so scan signature stays stable without a cache
    return jnp.zeros((n_steps or cfg.n_blocks,), jnp.float32)


# --------------------------------------------------------------------- #
# encoder (whisper backbone; frame embeddings come from the stub frontend)
# --------------------------------------------------------------------- #

def encoder_forward(embeds, params, cfg: ModelConfig, shd: Shardings):
    x = embeds + _sinusoid(cfg.encoder_seq, cfg.d_model).astype(embeds.dtype)
    x = shd.act(x, "batch", None, None)
    spec = LayerSpec("attn", "dense")
    no_rope = (None, None)

    def body(xc, p):
        h = L.apply_norm(xc, p["ln1"], cfg)
        q, k, v = L._qkv(h, p["attn"], cfg, shd, want_rope=False)
        o = _plain_attention(q, k, v, cfg, causal=False)
        xc = xc + L.attn_out(o, p["attn"], xc.dtype, shd)
        h = L.apply_norm(xc, p["ln2"], cfg)
        xc = xc + L.mlp_forward(h, p["mlp"], cfg, shd)
        return shd.act(xc, "batch", None, None), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return L.apply_norm(x, params["final_norm"], cfg)


def _sinusoid(s, d):
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], -1)[None]


# --------------------------------------------------------------------- #
# full forward
# --------------------------------------------------------------------- #

def mask_vocab_padding(logits, cfg: ModelConfig):
    """Mask Megatron-style vocab padding out of the softmax. Shared by
    `forward` and the dispatch decode step (serve.dispatch_engine), whose
    correctness contract is exact numerical agreement with forward."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))


def forward(params, cfg: ModelConfig, shd: Shardings, *,
            tokens=None, embeds=None, positions=None, mrope_positions=None,
            cache=None, encoder_embeds=None):
    """Returns (logits, new_cache, aux)."""
    if embeds is not None:
        x = embeds.astype(cfg.dtype)
        b, s = x.shape[:2]
    else:
        b, s = tokens.shape
        x = params["embed"][tokens].astype(cfg.dtype)
    x = shd.act(x, "batch", None, None)

    index = cache["index"] if cache is not None else jnp.zeros((), jnp.int32)
    if positions is None:
        positions = index + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    if cfg.rope == "mrope":
        if mrope_positions is None:
            mrope_positions = jnp.broadcast_to(positions[None], (3, b, s))
        rope = L.rope_sincos(mrope_positions, cfg)
    elif cfg.rope == "none":
        rope = (None, None)
    else:
        rope = L.rope_sincos(positions, cfg)

    encoder_out = None
    if cfg.encoder_layers:
        if encoder_embeds is not None:
            encoder_out = encoder_forward(encoder_embeds.astype(cfg.dtype),
                                          params["encoder"], cfg, shd)
        # else: decode step, cross-KV comes from the cache

    width = 0
    cache_layers = None
    attn_index = index
    if cache is not None:
        cache_layers = cache["layers"]
        width = _cache_seq_width(cache_layers)
        if s == 1:
            # per-row index (continuous batching: slots at skewed positions)
            attn_index = positions[:, -1]

    x, new_layers, aux = stack_forward(
        x, params, cfg, shd, rope, cache_layers, attn_index, width,
        encoder_out)

    x = L.apply_norm(x, params["final_norm"], cfg)
    wv = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = jnp.einsum("bsd,vd->bsv", x, wv.astype(x.dtype))
    logits = shd.act(logits, "batch", None, "vocab")
    logits = mask_vocab_padding(logits, cfg)

    new_cache = None
    if cache is not None:
        new_index = index + s
        if s == 1:
            # global index tracks the furthest-advanced slot
            new_index = jnp.maximum(new_index,
                                    jnp.max(positions[:, -1]) + 1).astype(jnp.int32)
        new_cache = dict(cache, index=new_index, layers=new_layers)
    return logits, new_cache, aux


def _cache_seq_width(cache_layers) -> int:
    for sl in cache_layers:
        if "k" in sl:
            return sl["k"].shape[2]  # (blocks, B, W, KVH, hd)
    return 0


# --------------------------------------------------------------------- #
# loss
# --------------------------------------------------------------------- #

def lm_loss(logits, labels, aux=0.0, aux_weight: float = 0.01):
    """Mean token cross-entropy; vocab-sharded-safe (one-hot contraction)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    ll = jnp.sum(lf * onehot, axis=-1)
    return jnp.mean(lse - ll) + aux_weight * aux
