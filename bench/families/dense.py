"""The dense GQA decoder family: its program config, its weight layout and
the program's parameter tree built from those weights, without copies.

Layout (L layers stacked on the first axis, V the padded vocabulary):
  embed (V, d)   unembed (V, d), unless tied   final_scale (d,)   [final_bias (d,)]
  per layer: ln1_scale, ln2_scale (d,) [ln1_bias, ln2_bias (d,)],
  wq (d, h, hd), wk, wv (d, kvh, hd), wo (h, hd, d) [bq (h, hd), bk, bv
  (kvh, hd)], wu (d, ff), [wg (d, ff)], wd (ff, d).
"""

from __future__ import annotations

import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.weights import check_tree  # noqa: E402


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.models import ModelConfig
    act = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}[conf["hidden_act"]]
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        sliding_window=int(conf["sliding_window"] or 0),
        attn_bias=bool(conf["attention_bias"]), mlp_act=act,
        gated_mlp=conf["mlp"] == "gated",
        norm_eps=conf.get("rms_norm_eps", conf.get("norm_epsilon")),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"], **conf["program"])


def leaf_table(conf: dict, padded_vocab: int) -> dict:
    """name -> (shape, stack, init, std), as `weights.make_weights` reads
    it. Norm scales are drawn around 1 and biases around 0, so that a path
    that drops either changes the result."""
    d, h, kvh = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    n = conf["num_hidden_layers"]
    ln = conf["norm"] == "layernorm"
    tied = conf["tie_word_embeddings"]
    # a tied matrix is also the head: drawn at the head's scale, so that
    # logits keep the same size either way
    t = {"embed": ((padded_vocab, d), 0, "normal",
                   1 / math.sqrt(d) if tied else 1.0),
         "final_scale": ((d,), 0, "scale", 0.1)}
    if not tied:
        t["unembed"] = ((padded_vocab, d), 0, "normal", 1 / math.sqrt(d))
    if ln:
        t["final_bias"] = ((d,), 0, "normal", 0.1)
    for name in ("ln1", "ln2"):
        t[f"{name}_scale"] = ((d,), n, "scale", 0.1)
        if ln:
            t[f"{name}_bias"] = ((d,), n, "normal", 0.1)
    t["wq"] = ((d, h, hd), n, "normal", 1 / math.sqrt(d))
    t["wk"] = ((d, kvh, hd), n, "normal", 1 / math.sqrt(d))
    t["wv"] = ((d, kvh, hd), n, "normal", 1 / math.sqrt(d))
    t["wo"] = ((h, hd, d), n, "normal", 1 / math.sqrt(h * hd))
    if conf["attention_bias"]:
        t["bq"] = ((h, hd), n, "normal", 0.1)
        t["bk"] = ((kvh, hd), n, "normal", 0.1)
        t["bv"] = ((kvh, hd), n, "normal", 0.1)
    t["wu"] = ((d, ff), n, "normal", 1 / math.sqrt(d))
    if conf["mlp"] == "gated":
        t["wg"] = ((d, ff), n, "normal", 1 / math.sqrt(d))
    t["wd"] = ((ff, d), n, "normal", 1 / math.sqrt(ff))
    return t


def to_program_tree(w: dict, conf: dict, shapes) -> dict:
    """The same arrays, grouped as the program's parameter tree, whose
    shapes `shapes` gives: one stacked `{ln1, attn, ln2, mlp}` layer."""
    def norm(prefix):
        n = {"scale": w[f"{prefix}_scale"]}
        if f"{prefix}_bias" in w:
            n["bias"] = w[f"{prefix}_bias"]
        return n

    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in w}
    mlp = {k: w[k] for k in ("wu", "wg", "wd") if k in w}
    tree = {"embed": w["embed"], "final_norm": norm("final"),
            "layers": [{"ln1": norm("ln1"), "attn": attn, "ln2": norm("ln2"),
                        "mlp": mlp}]}
    if "unembed" in w:
        tree["unembed"] = w["unembed"]
    return check_tree(tree, shapes)
