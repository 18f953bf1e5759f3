"""Random weights from ``--seed``, made on the device in one jitted call.

The tree has the layout the program stores (leaf names and shapes from
``jax.eval_shape`` of its initializer), in the dtype it serves them in.
Names decide the values: the embedding is N(0, 0.02) with its rows past the
published vocabulary zero (storage padding, which the program masks out of
the logits), a leaf whose name ends in ``_np`` (a placeholder that holds no
parameter) is zero, and every other matrix is N(0, 1/fan_in), fan-in being
its second-to-last axis. The reference reads the same tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative integer seed (wider than 32 bits
    too), one independent stream per ``stream``."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def leaf_names(tree) -> list:
    """Dotted path of each leaf, in the tree's flattening order."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
            for p, _ in paths]


def make_weights(seed: int, shapes, vocab: int):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes``."""
    names = leaf_names(shapes)
    leaves, treedef = jax.tree.flatten(shapes)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for name, leaf, k in zip(names, leaves, keys):
            if name.endswith("_np"):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
                continue
            x = jax.random.normal(k, leaf.shape, jnp.float32)
            if name == "embed":
                rows = jnp.arange(leaf.shape[0])[:, None] < vocab
                x = jnp.where(rows, x * 0.02, 0.0)
            else:
                x = x / np.sqrt(leaf.shape[-2])
            out.append(x.astype(leaf.dtype))
        return jax.tree.unflatten(treedef, out)

    return build(seed_key(seed))
