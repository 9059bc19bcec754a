"""Operations and bytes of the toy hybrid's steps, from its shapes: every
weight matrix read once per step (the tied head included), keys and
values read for the positions attended and written for the new ones, and
each Mamba layer's state read and written once per sequence and step."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _shapes(conf: dict):
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    h, kvh, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                  conf["head_dim"])
    di, ds = conf["mamba_expand"] * d, conf["mamba_d_state"]
    r, k = conf["mamba_dt_rank"], conf["mamba_d_conv"]
    n_attn = conf["num_hidden_layers"] // conf["attn_layer_period"]
    n_mamba = conf["num_hidden_layers"] - n_attn
    attn = 2 * d * h * hd + 2 * d * kvh * hd + 3 * d * ff
    mamba = (2 * d * di + di * (r + 2 * ds) + r * di + di * d + 3 * d * ff)
    small = n_attn * 2 * d + n_mamba * (2 * d + k * di + 3 * di + di * ds)
    state = di * ds * 4 + (k - 1) * di * _ITEMSIZE[conf["torch_dtype"]]
    return (d, h, kvh, hd, di, ds, n_attn, n_mamba, attn, mamba, small,
            state)


def _step(conf: dict, tokens: int, attended: int, sequences: int):
    (d, h, kvh, hd, di, ds, n_attn, n_mamba, attn, mamba, small,
     state) = _shapes(conf)
    it = _ITEMSIZE[conf["torch_dtype"]]
    vocab = conf["vocab_size"]
    flops = (2 * tokens * (n_attn * attn + n_mamba * mamba)
             + n_attn * 4 * h * hd * attended
             + n_mamba * 6 * tokens * di * ds + 2 * sequences * d * vocab)
    nbytes = (it * (n_attn * attn + n_mamba * mamba + small + vocab * d + d)
              + it * n_attn * 2 * kvh * hd * (attended + tokens)
              + n_mamba * 2 * state * sequences)
    return float(flops), float(nbytes)


def decode_step(conf: dict, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of one batched decode step at `positions`."""
    b = len(positions)
    return _step(conf, b, sum(int(p) + 1 for p in positions), b)


def prefill(conf: dict, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of prefilling one sequence of `n` tokens."""
    return _step(conf, n, n * (n + 1) // 2, 1)
