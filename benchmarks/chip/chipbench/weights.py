"""Seeded random weights, made on the device in one jitted call, in the
type they are served in, laid out as the program's parameter tree
(``DecoderLM``: layer-stacked ``layers``, ``embed`` [V, d] used as the tied
head, learned ``pos_table``; norm gains stored as ``scale`` with gain
= 1 + scale).  tests/test_chipbench_weights.py checks the layout against
the program's own ``init``.

Every leaf draws from its own key, ``fold_in(key(seed), leaf index)``, and
every value is a normal with the configuration's ``init`` standard
deviation for that leaf.  The plain reference reads these same arrays; it
never calls the program.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A key from any non-negative seed: PRNGKey keeps 32 bits, so the
    high bits are folded in rather than dropped."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(arch: dict) -> Dict:
    """{path: shape} of the served parameter tree."""
    L, d = arch["num_layers"], arch["d_model"]
    hd = arch.get("head_dim") or d // arch["num_heads"]
    qd, kvd = arch["num_heads"] * hd, arch["num_kv_heads"] * hd
    ff, V = arch["d_ff"], arch["vocab_size"]
    out = {
        "embed": (V, d),
        "pos_table": (arch["max_seq_len"], d),
        "layers/ln1/scale": (L, d), "layers/ln1/bias": (L, d),
        "layers/attn/wq": (L, d, qd), "layers/attn/wk": (L, d, kvd),
        "layers/attn/wv": (L, d, kvd), "layers/attn/wo": (L, qd, d),
        "layers/ln2/scale": (L, d), "layers/ln2/bias": (L, d),
        "layers/mlp/w_up": (L, d, ff), "layers/mlp/w_down": (L, ff, d),
        "final_norm/scale": (d,), "final_norm/bias": (d,),
    }
    if not arch.get("tie_embeddings", False):
        out["lm_head"] = (d, V)
    return out


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


def make_params(arch: dict, init: dict, seed: int, device=None):
    """The whole served tree on `device`, from `seed`, in one jitted call."""
    shp = shapes(arch)
    dtype = jnp.dtype(arch.get("dtype", "bfloat16"))
    paths = sorted(shp)

    def build(key):
        return _nest({p: (_std(init, p) * jax.random.normal(
            jax.random.fold_in(key, i), shp[p], jnp.float32)).astype(dtype)
            for i, p in enumerate(paths)})

    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)
