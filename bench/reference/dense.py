"""Plain float32 reference of a dense GQA decoder, in `jax.numpy` alone.

It follows the equations of the configuration file: RMSNorm or LayerNorm
with bias; rotary embeddings on the rotate-half convention; grouped-query
attention with optional q/k/v bias, causal and, where `sliding_window` is
set, limited to the last `sliding_window` positions; a gated SiLU or a
plain tanh-GELU MLP without bias; an output head of its own, or the
embedding matrix where the configuration ties them. Each departure
from the published model is listed in the configuration's `departures`.

No cache, no batching, no kernels: one sequence at a time, the whole
sequence at once, layer by layer, every matrix product at `HIGHEST`
precision. Attention is computed in blocks of queries so that a long
sequence fits beside the served weights.

`control=True` computes the control: every matrix product of a
projection, the MLP or the head takes float8 (e4m3) operands, weights
scaled per output channel and activations per row, and keys and values
are held in float8 per row and head. It is the step below the bfloat16
that the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _f8(x, axis):
    """float8 (e4m3) rounding of `x`, scaled along `axis` so that its
    largest magnitude maps to the format's largest, returned in f32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, w, control: bool):
    """(n, k) @ (k, m) in float32; the control rounds both to float8."""
    if control:
        a, w = _f8(a, 1), _f8(w, 0)
    return jnp.dot(a, w, precision=HIGHEST)


def _norm(x, scale, bias, kind: str, eps: float):
    if kind == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale + bias
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (n, heads, hd); rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window: int):
    """q (n, h, hd), k/v (n, kvh, hd), causal; in blocks of Q_BLOCK
    queries."""
    n, h, hd = q.shape
    kvh = k.shape[1]
    g = h // kvh
    kpos = jnp.arange(n)
    nb = min(Q_BLOCK, n)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * nb, nb, 0)
        qb = qb.reshape(nb, kvh, g, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
        s = s / math.sqrt(hd)
        qpos = i * nb + jnp.arange(nb)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
        return o.reshape(nb, h * hd)

    return jax.lax.map(block, jnp.arange(n // nb)).reshape(n, h * hd)


@functools.partial(jax.jit, static_argnames=("conf", "control"))
def _stack(x, layers, conf: tuple, control: bool):
    """Every layer over x (n, d), float32."""
    c = dict(conf)
    n = x.shape[0]
    d, h, kvh, hd = c["d"], c["h"], c["kvh"], c["hd"]
    pos = jnp.arange(n)

    def layer(x, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        y = _norm(x, w["ln1_scale"], w.get("ln1_bias"), c["norm"], c["eps"])
        q = _mm(y, w["wq"].reshape(d, h * hd), control).reshape(n, h, hd)
        k = _mm(y, w["wk"].reshape(d, kvh * hd), control).reshape(n, kvh, hd)
        v = _mm(y, w["wv"].reshape(d, kvh * hd), control).reshape(n, kvh, hd)
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q, k = _rope(q, pos, c["theta"]), _rope(k, pos, c["theta"])
        if control:
            k, v = _f8(k, 2), _f8(v, 2)
        o = _attention(q, k, v, c["window"])
        x = x + _mm(o, w["wo"].reshape(h * hd, d), control)
        y = _norm(x, w["ln2_scale"], w.get("ln2_bias"), c["norm"], c["eps"])
        u = _mm(y, w["wu"], control)
        if "wg" in w:
            u = jax.nn.silu(_mm(y, w["wg"], control)) * u
        else:
            u = jax.nn.gelu(u, approximate=True)
        return x + _mm(u, w["wd"], control), None

    x, _ = jax.lax.scan(layer, x, layers)
    return x


@functools.partial(jax.jit, static_argnames=("conf", "control"))
def _head(x, rows, scale, bias, head, conf: tuple, control: bool):
    c = dict(conf)
    y = _norm(x[rows], scale.astype(jnp.float32),
              None if bias is None else bias.astype(jnp.float32),
              c["norm"], c["eps"])
    return _mm(y, head[: c["vocab"]].astype(jnp.float32).T, control)


LAYER_KEYS = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "wq", "wk",
              "wv", "wo", "bq", "bk", "bv", "wu", "wg", "wd")


def static_conf(conf: dict) -> tuple:
    """The hashable subset of a configuration file the reference reads."""
    return tuple(sorted({
        "d": conf["hidden_size"], "h": conf["num_attention_heads"],
        "kvh": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "vocab": conf["vocab_size"], "norm": conf["norm"],
        "eps": conf.get("rms_norm_eps", conf.get("norm_epsilon")),
        "theta": float(conf["rope_theta"]),
        "window": int(conf["sliding_window"] or 0)}.items()))


def logits(weights: dict, conf: dict, tokens, rows, *, control: bool = False,
           bucket: int = 1024) -> np.ndarray:
    """Logits (len(rows), vocab_size) in float32 at sequence positions
    `rows` of `tokens`. The sequence is padded at its end to a multiple of
    `bucket` (causal attention keeps padding out of earlier positions), so
    one compiled program serves every length within a bucket; a bucket
    below `Q_BLOCK` or a multiple of it."""
    sc = static_conf(conf)
    n = len(tokens)
    padded = -(-n // bucket) * bucket
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    x = weights["embed"][jnp.asarray(toks)].astype(jnp.float32)
    layers = {k: weights[k] for k in LAYER_KEYS if k in weights}
    x = _stack(x, layers, sc, control)
    head = weights.get("unembed", weights["embed"])
    out = _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                weights["final_scale"], weights.get("final_bias"), head, sc,
                control)
    return np.asarray(out)
