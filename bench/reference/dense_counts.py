"""Operations and bytes a dense GQA decoder's steps need, from the
configuration's shapes alone: what the algorithm needs, never what a
program happens to do.

- every weight matrix is read once per step; embedding rows only for the
  tokens present, and not at all where the head, read whole, is the
  embedding matrix;
- keys and values are read only for the positions each sequence attends,
  min(position + 1, window), and only the new rows are written;
- a prefill computes logits for its last position only.

Weights and the cache are counted at the configuration's `torch_dtype`.
"""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(conf: dict):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"],
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"], int(conf["sliding_window"] or 0))


def layer_matmul_params(conf: dict) -> int:
    """Weights of one layer's matrix products."""
    d, h, kvh, hd, ff, _, _, _ = _dims(conf)
    n_mlp = 3 if conf["mlp"] == "gated" else 2
    return d * h * hd * 2 + 2 * d * kvh * hd + n_mlp * d * ff


def layer_small_params(conf: dict) -> int:
    """Norm scales and biases of one layer."""
    d, h, kvh, hd, *_ = _dims(conf)
    n = 2 * d * (2 if conf["norm"] == "layernorm" else 1)
    if conf["attention_bias"]:
        n += (h + 2 * kvh) * hd
    return n


def _attended(pos: int, window: int) -> int:
    return min(pos + 1, window) if window else pos + 1


def decode_step(conf: dict, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of one batched decode step in which the sequence of
    each live slot processes the token at `positions[i]`."""
    d, h, kvh, hd, ff, vocab, n_layers, window = _dims(conf)
    b = len(positions)
    it = _ITEMSIZE[conf["torch_dtype"]]
    attended = sum(_attended(int(p), window) for p in positions)
    flops = n_layers * (2 * b * layer_matmul_params(conf)
                        + 4 * h * hd * attended)
    flops += 2 * b * d * vocab
    weights = (n_layers * (layer_matmul_params(conf) + layer_small_params(conf))
               + vocab * d + d * (2 if conf["norm"] == "layernorm" else 1))
    kv_row = 2 * kvh * hd                       # k and v of one position
    rows = 0 if conf["tie_word_embeddings"] else b * d
    nbytes = it * (weights + rows                       # embedding rows
                   + n_layers * kv_row * attended       # keys/values read
                   + n_layers * kv_row * b)             # new rows written
    return float(flops), float(nbytes)


def prefill(conf: dict, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of prefilling one sequence of `n` tokens."""
    d, h, kvh, hd, ff, vocab, n_layers, window = _dims(conf)
    it = _ITEMSIZE[conf["torch_dtype"]]
    attended = sum(_attended(p, window) for p in range(n))
    flops = n_layers * (2 * n * layer_matmul_params(conf)
                        + 4 * h * hd * attended)
    flops += 2 * d * vocab
    weights = (n_layers * (layer_matmul_params(conf) + layer_small_params(conf))
               + vocab * d + d * (2 if conf["norm"] == "layernorm" else 1))
    kv_row = 2 * kvh * hd
    kept = min(n, window) if window else n
    rows = 0 if conf["tie_word_embeddings"] else n * d
    nbytes = it * (weights + rows + n_layers * kv_row * kept)
    return float(flops), float(nbytes)
