"""A toy family: attention every `attn_layer_period` layers, Mamba-1 in the
others, each followed by a gated SiLU MLP, RMSNorm throughout.

Layout (V the padded vocabulary; A attention layers and M Mamba layers,
each kind stacked in depth order on its own first axis):
  embed (V, d)   final_scale (d,)   [unembed (V, d)]
  attn_*: ln1_scale, ln2_scale (d,), wq (d, h, hd), wk, wv (d, kvh, hd),
    wo (h, hd, d), wu, wg (d, ff), wd (ff, d)
  mamba_*: ln1_scale, ln2_scale (d,), in_proj (d, 2 di), conv_w (k, di),
    conv_b (di,), x_proj (di, r + 2 ds), dt_proj (r, di), dt_bias (di,),
    A_log (di, ds), D (di,), out_proj (di, d), wu, wg (d, ff), wd (ff, d)
"""

from __future__ import annotations

import math
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.weights import check_tree  # noqa: E402

ATTN = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "wu", "wg", "wd")
MAMBA = ("ln1_scale", "ln2_scale", "in_proj", "conv_w", "conv_b", "x_proj",
         "dt_proj", "dt_bias", "A_log", "D", "out_proj", "wu", "wg", "wd")


def counts(conf: dict) -> tuple[int, int]:
    """(attention layers, Mamba layers)."""
    n_attn = conf["num_hidden_layers"] // conf["attn_layer_period"]
    return n_attn, conf["num_hidden_layers"] - n_attn


def program_config(conf: dict):
    from repro.models import ModelConfig
    return ModelConfig(
        name=conf["name"], family="hybrid",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        attn_layer_period=conf["attn_layer_period"],
        attn_layer_offset=conf["attn_layer_offset"],
        mlp_act=conf["hidden_act"], gated_mlp=True, ssm_type="mamba",
        ssm_d_state=conf["mamba_d_state"], ssm_d_conv=conf["mamba_d_conv"],
        ssm_expand=conf["mamba_expand"], ssm_dt_rank=conf["mamba_dt_rank"],
        norm_eps=conf["rms_norm_eps"],
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        dtype=conf["torch_dtype"], **conf["program"])


def leaf_table(conf: dict, padded_vocab: int) -> dict:
    d, h, kvh = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    di, ds = conf["mamba_expand"] * d, conf["mamba_d_state"]
    k, r = conf["mamba_d_conv"], conf["mamba_dt_rank"]
    na, nm = counts(conf)
    tied = conf["tie_word_embeddings"]
    t = {"embed": ((padded_vocab, d), 0, "normal",
                   1 / math.sqrt(d) if tied else 1.0),
         "final_scale": ((d,), 0, "scale", 0.1)}
    if not tied:
        t["unembed"] = ((padded_vocab, d), 0, "normal", 1 / math.sqrt(d))
    for prefix, n in (("attn", na), ("mamba", nm)):
        t[f"{prefix}_ln1_scale"] = ((d,), n, "scale", 0.1)
        t[f"{prefix}_ln2_scale"] = ((d,), n, "scale", 0.1)
        t[f"{prefix}_wu"] = ((d, ff), n, "normal", 1 / math.sqrt(d))
        t[f"{prefix}_wg"] = ((d, ff), n, "normal", 1 / math.sqrt(d))
        t[f"{prefix}_wd"] = ((ff, d), n, "normal", 1 / math.sqrt(ff))
    t["attn_wq"] = ((d, h, hd), na, "normal", 1 / math.sqrt(d))
    t["attn_wk"] = ((d, kvh, hd), na, "normal", 1 / math.sqrt(d))
    t["attn_wv"] = ((d, kvh, hd), na, "normal", 1 / math.sqrt(d))
    t["attn_wo"] = ((h, hd, d), na, "normal", 1 / math.sqrt(h * hd))
    t["mamba_in_proj"] = ((d, 2 * di), nm, "normal", 1 / math.sqrt(d))
    t["mamba_conv_w"] = ((k, di), nm, "normal", 1 / math.sqrt(k))
    t["mamba_conv_b"] = ((di,), nm, "normal", 0.1)
    t["mamba_x_proj"] = ((di, r + 2 * ds), nm, "normal", 1 / math.sqrt(di))
    t["mamba_dt_proj"] = ((r, di), nm, "normal", 1 / math.sqrt(r))
    t["mamba_dt_bias"] = ((di,), nm, "normal", 0.1)
    t["mamba_A_log"] = ((di, ds), nm, "scale", 0.1)
    t["mamba_D"] = ((di,), nm, "scale", 0.1)
    t["mamba_out_proj"] = ((di, d), nm, "normal", 1 / math.sqrt(di))
    return t


def to_program_tree(w: dict, conf: dict, shapes) -> dict:
    """One stacked layer per position of the period; a Mamba position
    takes every (period - 1)-th of the stacked Mamba leaves."""
    period, offset = conf["attn_layer_period"], conf["attn_layer_offset"]
    na, _ = counts(conf)

    def layer(prefix, pick):
        g = {k: pick(w[f"{prefix}_{k}"]) for k in
             (ATTN if prefix == "attn" else MAMBA)}
        out = {"ln1": {"scale": g.pop("ln1_scale")},
               "ln2": {"scale": g.pop("ln2_scale")},
               "mlp": {k: g.pop(k) for k in ("wu", "wg", "wd")}}
        out[prefix] = g
        return out

    layers = []
    for i in range(period):
        if i == offset:
            layers.append(layer("attn", lambda a: a))
        else:
            j = i if i < offset else i - 1
            layers.append(layer("mamba", lambda a, j=j: a.reshape(
                (na, period - 1) + a.shape[1:])[:, j]))
    tree = {"embed": w["embed"], "final_norm": {"scale": w["final_scale"]},
            "layers": layers}
    if "unembed" in w:
        tree["unembed"] = w["unembed"]
    return check_tree(tree, shapes)
