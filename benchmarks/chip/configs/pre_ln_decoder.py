"""Plain float32 reference of a pre-LayerNorm decoder-only transformer, the
GPT-2 / OPT layer: x += Attn(LN1(x)); x += W_down GELU(W_up LN2(x)); a final
LN, then logits against the tied token embedding.  Learned absolute
positions are added to the token embedding; attention is causal multi-head
softmax(q k^T / sqrt(d_head)) v.  GELU is the tanh form (GPT-2's
``gelu_new``).  A norm's gain is 1 + ``scale``.  No linear layer has a bias
(the served model has none; the configuration files list this departure).

Written in straightforward jax.numpy, with every matmul at
``Precision.HIGHEST``, and it imports nothing of the program.  It runs one
layer at a time on the device so that it fits beside nothing else: each
layer's weights are cast to float32 on the device, every sequence passes
through it, and they are freed before the next layer.

``control=True`` is the same forward with every matmul operand (weights,
activations, attention probabilities) rounded to float8 e4m3 with a
per-tensor scale and accumulated in float32: the next precision below the
bfloat16 the configurations serve in.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(spec, a, b, control):
    if control:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + scale) + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


@partial(jax.jit, static_argnames=("heads", "eps", "control"))
def block(x, lp, *, heads: int, eps: float, control: bool):
    """One layer over one sequence x [S, d]."""
    s, d = x.shape
    h = layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"], eps)
    a = lp["attn"]
    q = _mm("sd,de->se", h, a["wq"], control).reshape(s, heads, -1)
    k = _mm("sd,de->se", h, a["wk"], control).reshape(s, heads, -1)
    v = _mm("sd,de->se", h, a["wv"], control).reshape(s, heads, -1)
    scores = _mm("qhd,khd->hqk", q, k, control) / np.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :, None] >= jnp.arange(s)[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, control).reshape(s, -1)
    x = x + _mm("se,ed->sd", o, a["wo"], control)
    h = layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"], eps)
    m = lp["mlp"]
    return x + _mm("sf,fd->sd", gelu_tanh(_mm("sd,df->sf", h, m["w_up"],
                                                control)),
                   m["w_down"], control)


@jax.jit
def embed(tokens, table, pos_table):
    return table[tokens] + pos_table[: tokens.shape[0]]


@partial(jax.jit, static_argnames=("eps", "control"))
def head_stats(x, final_norm, table, targets, *, eps: float, control: bool):
    """Per position: the best logit, the logit of each target column, the
    logits' standard deviation over the vocabulary, and the top token."""
    h = layer_norm(x, final_norm["scale"], final_norm["bias"], eps)
    logits = _mm("sd,vd->sv", h, table, control)
    at = jnp.take_along_axis(logits, targets, axis=1)
    return {"max": jnp.max(logits, axis=1), "at": at,
            "std": jnp.std(logits, axis=1),
            "top": jnp.argmax(logits, axis=1).astype(jnp.int32)}


def _f32(tree, device):
    return jax.tree.map(lambda a: jax.device_put(a, device)
                        .astype(jnp.float32), tree)


def forward_stats(params: Dict, arch: dict, seqs: Sequence[np.ndarray],
                  targets: Sequence[np.ndarray], *, pad_to: int,
                  control: bool = False, device=None) -> List[Dict]:
    """`seqs[i]` (token ids) padded to `pad_to`; `targets[i]` [pad_to, K]
    int32 token ids whose logits to read at each position.  `params` is
    the host tree of served (bfloat16) weights.  Returns, per sequence, the
    `head_stats` arrays as numpy."""
    device = device or jax.devices()[0]
    eps = float(arch.get("layer_norm_epsilon", 1e-5))
    heads = arch["num_heads"]
    table = jax.device_put(params["embed"], device).astype(jnp.float32)
    pos = jax.device_put(params["pos_table"], device).astype(jnp.float32)
    xs = []
    for t in seqs:
        ids = np.zeros(pad_to, np.int32)
        ids[: len(t)] = t
        xs.append(embed(jax.device_put(ids, device), table, pos))
    del pos
    layers = params["layers"]
    for li in range(arch["num_layers"]):
        lp = _f32(jax.tree.map(lambda a: a[li], layers), device)
        xs = [block(x, lp, heads=heads, eps=eps, control=control) for x in xs]
        del lp
    fn = _f32(params["final_norm"], device)
    out = []
    for x, tg in zip(xs, targets):
        st = head_stats(x, fn, table, jax.device_put(tg, device), eps=eps,
                        control=control)
        out.append({k: np.asarray(v) for k, v in st.items()})
    return out
