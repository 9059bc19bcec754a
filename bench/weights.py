"""Random weights of a dense GQA decoder, made on the device from the seed
in one jitted call, in the type they are served in.

The benchmark owns the weights: the program under test and the reference
are both handed these arrays. The layout is the benchmark's own (below);
`to_program_tree` regroups the same arrays, without copies, into the tree
the program's parameter definitions ask for.

Layout (L layers stacked on the first axis, V the padded vocabulary):
  embed (V, d)   unembed (V, d), unless tied   final_scale (d,)   [final_bias (d,)]
  per layer: ln1_scale, ln2_scale (d,) [ln1_bias, ln2_bias (d,)],
  wq (d, h, hd), wk, wv (d, kvh, hd), wo (h, hd, d) [bq (h, hd), bk, bv
  (kvh, hd)], wu (d, ff), [wg (d, ff)], wd (ff, d).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64-bit ones included."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def leaf_table(conf: dict, padded_vocab: int) -> dict:
    """name -> (shape of one layer or of the whole leaf, per_layer, init,
    std). Norm scales are drawn around 1 and biases around 0, so that a
    path that drops either changes the result."""
    d, h, kvh = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    ln = conf["norm"] == "layernorm"
    tied = conf["tie_word_embeddings"]
    # a tied matrix is also the head: drawn at the head's scale, so that
    # logits keep the same size either way
    t = {"embed": ((padded_vocab, d), False, "normal",
                   1 / math.sqrt(d) if tied else 1.0),
         "final_scale": ((d,), False, "scale", 0.1)}
    if not tied:
        t["unembed"] = ((padded_vocab, d), False, "normal", 1 / math.sqrt(d))
    if ln:
        t["final_bias"] = ((d,), False, "normal", 0.1)
    for n in ("ln1", "ln2"):
        t[f"{n}_scale"] = ((d,), True, "scale", 0.1)
        if ln:
            t[f"{n}_bias"] = ((d,), True, "normal", 0.1)
    t["wq"] = ((d, h, hd), True, "normal", 1 / math.sqrt(d))
    t["wk"] = ((d, kvh, hd), True, "normal", 1 / math.sqrt(d))
    t["wv"] = ((d, kvh, hd), True, "normal", 1 / math.sqrt(d))
    t["wo"] = ((h, hd, d), True, "normal", 1 / math.sqrt(h * hd))
    if conf["attention_bias"]:
        t["bq"] = ((h, hd), True, "normal", 0.1)
        t["bk"] = ((kvh, hd), True, "normal", 0.1)
        t["bv"] = ((kvh, hd), True, "normal", 0.1)
    t["wu"] = ((d, ff), True, "normal", 1 / math.sqrt(d))
    if conf["mlp"] == "gated":
        t["wg"] = ((d, ff), True, "normal", 1 / math.sqrt(d))
    t["wd"] = ((ff, d), True, "normal", 1 / math.sqrt(ff))
    return t


def make_weights(conf: dict, padded_vocab: int, seed: int):
    """All weights in one jitted call, in the configuration's
    `torch_dtype`. Layer leaves are drawn a layer at a
    time inside the call, so no full-size f32 temporary exists."""
    table = leaf_table(conf, padded_vocab)
    dtype = jnp.dtype(conf["torch_dtype"])
    n_layers = conf["num_hidden_layers"]
    names = sorted(table)

    def draw(key, shape, init, std):
        z = jax.random.normal(key, shape, jnp.float32) * std
        return (z + 1.0 if init == "scale" else z).astype(dtype)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, per_layer, init, std = table[name]
            k = jax.random.fold_in(key, i)
            if per_layer:
                keys = jax.vmap(lambda l: jax.random.fold_in(k, l))(
                    jnp.arange(n_layers))
                out[name] = jax.lax.map(
                    lambda kk: draw(kk, shape, init, std), keys)
            else:
                out[name] = draw(k, shape, init, std)
        return out

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def to_program_tree(w: dict, conf: dict, shapes) -> dict:
    """The same arrays, grouped as the program's parameter tree, whose
    shapes `shapes` gives (the program's own `param_shape_structs`).
    Fails loudly where the program asks for a leaf or a shape this layout
    does not hold."""
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
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), shapes)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)
    if want != got:
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's weights:\n{want}\n!=\n{got}")
    return tree
