"""Plain float32 reference of the toy attention and Mamba-1 hybrid, in
`jax.numpy` alone: one sequence at a time, every matrix product at
`HIGHEST` precision, the selective scan as its recurrence, one position
after another. Attention, norms, rotary embeddings and the float8 control
are the dense reference's."""

from __future__ import annotations

import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.reference.dense import (  # noqa: E402
    _attention, _f8, _mm, _norm, _rope)


def _mlp(x, w, control):
    up = jax.nn.silu(_mm(x, w["wg"], control)) * _mm(x, w["wu"], control)
    return _mm(up, w["wd"], control)


def _attn(y, w, c, control):
    n = y.shape[0]
    d, h, kvh, hd = c["d"], c["h"], c["kvh"], c["hd"]
    pos = jnp.arange(n)
    q = _mm(y, w["wq"].reshape(d, h * hd), control).reshape(n, h, hd)
    k = _mm(y, w["wk"].reshape(d, kvh * hd), control).reshape(n, kvh, hd)
    v = _mm(y, w["wv"].reshape(d, kvh * hd), control).reshape(n, kvh, hd)
    q, k = _rope(q, pos, c["theta"]), _rope(k, pos, c["theta"])
    if control:
        k, v = _f8(k, 2), _f8(v, 2)
    return _mm(_attention(q, k, v, 0), w["wo"].reshape(h * hd, d), control)


def _mamba(y, w, c, control):
    n, di, ds, r = y.shape[0], c["di"], c["ds"], c["r"]
    xin, z = jnp.split(_mm(y, w["in_proj"], control), 2, axis=-1)
    k = w["conv_w"].shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, di)), xin])
    u = jax.nn.silu(sum(xp[i:i + n] * w["conv_w"][i] for i in range(k))
                    + w["conv_b"])
    dt, b, cc = jnp.split(_mm(u, w["x_proj"], control), [r, r + ds], axis=-1)
    dt = jax.nn.softplus(_mm(dt, w["dt_proj"], control) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(hs, t):
        dt_t, u_t, b_t, c_t = t
        hs = jnp.exp(dt_t[:, None] * a) * hs + (dt_t * u_t)[:, None] * b_t
        return hs, hs @ c_t

    _, out = jax.lax.scan(step, jnp.zeros((di, ds)), (dt, u, b, cc))
    out = (out + u * w["D"]) * jax.nn.silu(z)
    return _mm(out, w["out_proj"], control)


@functools.partial(jax.jit, static_argnames=("conf", "control"))
def _forward(x, rows, weights, conf: tuple, control: bool):
    c = dict(conf)
    weights = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    seen = {"attn": 0, "mamba": 0}
    for layer in range(c["layers"]):
        kind = "attn" if layer % c["period"] == c["offset"] else "mamba"
        i = seen[kind]
        seen[kind] += 1
        w = {k[len(kind) + 1:]: v[i] for k, v in weights.items()
             if k.startswith(kind + "_")}
        y = _norm(x, w["ln1_scale"], None, "rmsnorm", c["eps"])
        x = x + (_attn if kind == "attn" else _mamba)(y, w, c, control)
        x = x + _mlp(_norm(x, w["ln2_scale"], None, "rmsnorm", c["eps"]),
                     w, control)
    y = _norm(x[rows], weights["final_scale"], None, "rmsnorm", c["eps"])
    head = weights.get("unembed", weights["embed"])
    return _mm(y, head[: c["vocab"]].T, control)


def logits(weights: dict, conf: dict, tokens, rows, *, control: bool = False,
           bucket: int = 1024) -> np.ndarray:
    """Logits (len(rows), vocab_size) in float32 at sequence positions
    `rows` of `tokens`, the sequence padded at its end to a multiple of
    `bucket` (both mixers are causal)."""
    sc = tuple(sorted({
        "d": conf["hidden_size"], "h": conf["num_attention_heads"],
        "kvh": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "di": conf["mamba_expand"] * conf["hidden_size"],
        "ds": conf["mamba_d_state"], "r": conf["mamba_dt_rank"],
        "layers": conf["num_hidden_layers"],
        "period": conf["attn_layer_period"],
        "offset": conf["attn_layer_offset"], "vocab": conf["vocab_size"],
        "eps": conf["rms_norm_eps"],
        "theta": float(conf["rope_theta"])}.items()))
    n = len(tokens)
    toks = np.zeros(-(-n // bucket) * bucket, np.int32)
    toks[:n] = tokens
    x = weights["embed"][jnp.asarray(toks)].astype(jnp.float32)
    out = _forward(x, jnp.asarray(np.asarray(rows, np.int32)), weights, sc,
                   control)
    return np.asarray(out)
