"""Random weights from ``--seed``, made by the benchmark on the device.

One jitted call draws every leaf in the layout the program serves
(``model_zoo.abstract_params`` of the same configuration) and in the
program's parameter dtype. The benchmark makes them, so the program
and the plain reference read the same numbers without the reference
taking anything the program made.

Each leaf's scale comes from the configuration's family module
(``leaf_std``); a leaf it gives no scale is a norm gain, drawn around
1. Biases and norm gains, which the program initialises to constants,
are drawn too so that the comparison exercises them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from loader import family


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    words = np.random.default_rng([int(seed), 7]).integers(
        0, 2**31 - 1, size=2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def build(abstract, c: dict, seed: int):
    """Params shaped like ``abstract`` (a ShapeDtypeStruct tree)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [path[-1].key for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    leaf_std = family(c).leaf_std

    def make(key):
        out = []
        for i, (name, leaf) in enumerate(zip(names, leaves)):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, leaf.shape, jnp.float32)
            std = leaf_std(name, leaf.shape, c)
            x = 1.0 + 0.1 * z if std is None else z * std
            out.append(x.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key_from_seed(seed))
