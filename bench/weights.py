"""Seeded random weights, drawn by the benchmark and not by the program.

Each weight is a function of ``--seed``, its name and (for the stacked
layer weights) the layer index, so the harness can draw the whole tree
in one jitted call on the device, in the served dtype, and the reference
can draw one layer at a time and get the same values.

Names follow the program's parameter tree (``layers/l0/attn/w_qkv``,
``embed/table``, ...).  The rule goes by rank, not by name, so that a
new family's leaves need none of their own: every vector (a norm's
scale, a qk-norm's) is 1 + 0.1 normal, so a norm whose scale is dropped
shows; the embedding table (also the LM head) is normal with standard
deviation d_model ** -0.5, so logits have a spread of about 1; every
other leaf, expert stacks [E, d, f] and [E, f, d] among them, is normal
with standard deviation fan_in ** -0.5, its fan-in the second-to-last
axis.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int):
    return jax.random.PRNGKey(seed)


def leaf_key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw(key, name: str, shape, d_model: int):
    """float32 values of one weight (one layer's slice for stacked ones)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:
        return 1.0 + 0.1 * x
    if name == "embed/table":
        return x * d_model ** -0.5
    return x * shape[-2] ** -0.5


def layer_weight(root, name: str, layer, shape, d_model: int, dtype):
    """One layer's slice of a stacked weight, as the served dtype holds
    it, in float32 (``layer`` may be traced)."""
    k = jax.random.fold_in(leaf_key(root, name), layer)
    return draw(k, name, shape, d_model).astype(dtype).astype(jnp.float32)


def global_weight(root, name: str, shape, d_model: int, dtype):
    return draw(leaf_key(root, name), name, shape, d_model).astype(
        dtype).astype(jnp.float32)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def tree_init(root, struct, d_model: int):
    """Values for a tree of ``ShapeDtypeStruct`` leaves, drawn from the
    key ``root``; leaves under ``layers`` stack one draw per layer along
    their first axis."""
    def one(path, leaf):
        name = path_name(path)
        k = leaf_key(root, name)
        if name.startswith("layers/"):
            keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(
                jnp.arange(leaf.shape[0]))
            vals = jax.vmap(lambda kk: draw(kk, name, leaf.shape[1:],
                                            d_model))(keys)
        else:
            vals = draw(k, name, leaf.shape, d_model)
        return vals.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, struct)
