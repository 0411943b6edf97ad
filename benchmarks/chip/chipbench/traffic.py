"""The one traffic generator: a closed-loop request stream from a mix's
parameters (a `traffic/<mix>.json` file) and the run's seed.

A mix file holds::

    clients       closed-loop clients (the engine's max_active)
    requests      length of the stream (more than any window finishes)
    prompt        {"dist": "lognormal", "median", "sigma", "min", "max"}
                  or {"dist": "uniform", "min", "max"}   (tokens, inclusive)
    output        the same, for the tokens each request generates
    max_total     prompt + output never exceeds this (the context)
    size_seed     seed of the sizes: every run seed gets the same sizes in
                  the same order, so every seed does the same work and
                  compiles the same shapes
    fill          optional: the window opens once this many clients' first
                  requests have emitted a token (default: every client)

The run's seed draws the prompts' token ids (uniform over the vocabulary);
the model's weights come from the same seed (`weights.py`).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def draw_lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = np.exp(np.log(float(dist["median"]))
                   + float(dist["sigma"]) * rng.standard_normal(n))
        out = np.rint(x)
    elif kind == "uniform":
        out = rng.integers(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(out, lo, hi).astype(np.int64)


def sizes(traffic: dict) -> List[Tuple[int, int]]:
    """(prompt_len, max_new) of every request, from `size_seed` alone."""
    n = int(traffic["requests"])
    rng = np.random.default_rng(int(traffic["size_seed"]))
    plens = draw_lengths(traffic["prompt"], n, rng)
    outs = draw_lengths(traffic["output"], n, rng)
    cap = int(traffic["max_total"])
    outs = np.minimum(outs, cap - plens)
    if np.any(outs < 1):
        raise ValueError("a prompt leaves no room for output under max_total")
    return [(int(p), int(o)) for p, o in zip(plens, outs)]


def make_stream(traffic: dict, vocab: int, seed: int
                ) -> List[Tuple[np.ndarray, int]]:
    """[(prompt token ids int32, max_new)] in send order."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, p).astype(np.int32), o)
            for p, o in sizes(traffic)]

