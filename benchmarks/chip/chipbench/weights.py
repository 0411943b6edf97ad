"""Seeded random weights, made on the device in one jitted call, laid out
as the tree the program's own ``init`` declares (``jax.eval_shape``), each
leaf in the type that tree gives it: the served dtype, or float32 where the
program keeps a leaf in float32 (an MoE router).  Nothing of the program's
initial values is used, only the tree's paths, shapes and dtypes.

Every leaf draws from its own key, ``fold_in(key(seed), i)`` with ``i`` the
leaf's index among the sorted '/'-joined paths, and every value is a float32
normal with the configuration's ``init`` standard deviation for that leaf,
cast to the leaf's dtype.  The plain reference reads these same arrays; it
never calls the program.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A key from any non-negative seed: PRNGKey keeps 32 bits, so the
    high bits are folded in rather than dropped."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def declared(model) -> Dict[str, Tuple[tuple, jnp.dtype]]:
    """{path: (shape, dtype)} of the tree ``model.init`` declares, with the
    path's dict keys joined by '/'."""
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): (leaf.shape, leaf.dtype)
            for path, leaf in leaves}


def _std(init: dict, path: str) -> float:
    """The configured std of a leaf: the most specific of its path's names."""
    for part in reversed(path.split("/")):
        if part in init:
            return float(init[part])
    raise KeyError(f"no init std for {path}")


def _nest(flat: Dict[str, object]) -> Dict:
    tree: Dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def make_params(model, init: dict, seed: int, device=None):
    """The whole served tree of `model` on `device`, from `seed`, in one
    jitted call."""
    leaves = declared(model)
    paths = sorted(leaves)
    stds = {p: _std(init, p) for p in paths}     # a missing std fails here

    def build(key):
        return _nest({p: (stds[p] * jax.random.normal(
            jax.random.fold_in(key, i), leaves[p][0], jnp.float32))
            .astype(leaves[p][1]) for i, p in enumerate(paths)})

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
