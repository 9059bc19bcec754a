"""Random weights made on the device from the seed in one jitted call, in
the type they are served in.

The benchmark owns the weights: the program under test and the reference
are both handed these arrays. Which arrays there are is the family's own
(`families/<family>.py`, `leaf_table`); this module draws them.

A table maps each leaf's name to (shape, stack, init, std): `shape` is the
shape of the whole leaf where `stack` is 0, otherwise of one of `stack`
copies stacked on a new first axis (one per layer of that kind). `init`
"normal" draws around 0 and "scale" around 1, each with `std`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole seed, 64-bit ones included."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


def make_weights(table: dict, dtype: str, seed: int):
    """All leaves of `table` in one jitted call, in `dtype`. Leaf `i` (in
    name order) takes the seed's key folded with `i`; copy `l` of a stacked
    leaf that key folded again with `l`, drawn one copy at a time inside
    the call, so no full-size float32 temporary exists."""
    dtype = jnp.dtype(dtype)
    names = sorted(table)

    def draw(key, shape, init, std):
        z = jax.random.normal(key, shape, jnp.float32) * std
        return (z + 1.0 if init == "scale" else z).astype(dtype)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            shape, stack, init, std = table[name]
            k = jax.random.fold_in(key, i)
            if stack:
                keys = jax.vmap(lambda l: jax.random.fold_in(k, l))(
                    jnp.arange(stack))
                out[name] = jax.lax.map(
                    lambda kk: draw(kk, shape, init, std), keys)
            else:
                out[name] = draw(k, shape, init, std)
        return out

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def check_tree(tree: dict, shapes) -> dict:
    """`tree`, once its leaves' shapes and types are those of `shapes`
    (the program's own `param_shape_structs`); fails loudly where the
    program asks for a leaf or a shape that the family's layout does not
    hold."""
    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), shapes)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)
    if want != got:
        raise ValueError(f"the program's parameter tree differs from the "
                         f"benchmark's weights:\n{want}\n!=\n{got}")
    return tree
