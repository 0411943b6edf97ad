"""The comparison that decides `correct`.

Every request that the window served at least one token is compared (the
finished ones and those still running when the window closed, whose tokens
are final too): its prompt followed by its served tokens runs once through
the configuration's plain float32 reference.  At each served position two
numbers are read, both in units of that position's reference-logit standard
deviation over the vocabulary:

* gap: how far the served token's reference logit lies below the
  reference's best logit.  Greedy decoding in exact arithmetic gives 0;
  rounding gives small gaps where the top logits nearly tie.
* logit error: how far the logit the engine sampled the token from (read
  by the sampler hook) lies from the reference's logit of that token.  It
  sees errors that leave the top token in place.

The widest of each is held to the configuration's limit.  A token
regenerated after a rollback must equal the token first emitted at that
index, since a client has already seen that one: that count is held to 0.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclass
class Item:
    rid: int
    prompt: np.ndarray
    served: List[int]
    logits: List[float] = field(default_factory=list)   # as served


def inputs(item: Item, pad_to: int, extra: Sequence[np.ndarray] = ()
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(token ids, targets [pad_to, 1 + len(extra)], served positions).
    Position p-1+i predicts served token i; `extra` are more target columns
    over the same positions (the control's top tokens)."""
    p, n = len(item.prompt), len(item.served)
    ids = np.concatenate([item.prompt, np.asarray(item.served[:-1], np.int32)])
    if len(ids) > pad_to:
        raise ValueError(f"request {item.rid}: {len(ids)} tokens > {pad_to}")
    pos = np.arange(p - 1, p - 1 + n)
    tg = np.zeros((pad_to, 1 + len(extra)), np.int32)
    tg[pos, 0] = item.served
    for j, e in enumerate(extra):
        tg[pos, j + 1] = e[pos]
    return ids.astype(np.int32), tg, pos


def gaps(stats: Dict[str, np.ndarray], pos: np.ndarray, col: int = 0
         ) -> np.ndarray:
    """Standardized gap of target column `col` below the best logit at the
    served positions."""
    return (stats["max"][pos] - stats["at"][pos, col]) / stats["std"][pos]


def logit_errors(stats: Dict[str, np.ndarray], pos: np.ndarray,
                 served: Sequence[float]) -> np.ndarray:
    return np.abs(np.asarray(served, np.float64) - stats["at"][pos, 0]) \
        / stats["std"][pos]


def compare(ref, params, arch: dict, config: dict, items: Sequence[Item],
            pad_to: int, device=None) -> Tuple[float, float, int]:
    """(widest gap, widest logit error, tokens compared) over `items`;
    `config` is the configuration file, whose own keys (a norm's epsilon,
    say) the reference reads under its source's names."""
    if not items:
        return float("nan"), float("nan"), 0
    built = [inputs(it, pad_to) for it in items]
    stats = ref.forward_stats(params, arch, config, [b[0] for b in built],
                              [b[1] for b in built], pad_to=pad_to,
                              device=device)
    g = np.concatenate([gaps(s, b[2]) for s, b in zip(stats, built)])
    e = np.concatenate([logit_errors(s, b[2], it.logits)
                        for s, b, it in zip(stats, built, items)])
    return float(np.max(g)), float(np.max(e)), int(g.size)


def control(ref, params, arch: dict, config: dict, items: Sequence[Item],
            pad_to: int, device=None) -> Dict[str, float]:
    """The control: the reference computed in float8 in the program's place,
    read at the served positions of the same prompts and tokens.  Its gap
    is that of the token float8 puts first; its logit error that of float8's
    logit of the served token.  The program's own numbers on the same
    positions come alongside."""
    built = [inputs(it, pad_to) for it in items]
    low = ref.forward_stats(params, arch, config, [b[0] for b in built],
                            [b[1] for b in built], pad_to=pad_to,
                            control=True, device=device)
    both = [inputs(it, pad_to, [s["top"]]) for it, s in zip(items, low)]
    stats = ref.forward_stats(params, arch, config, [b[0] for b in both],
                              [b[1] for b in both], pad_to=pad_to,
                              device=device)
    cat = np.concatenate
    return {
        "control_gap": float(np.max(cat([gaps(s, b[2], 1)
                                         for s, b in zip(stats, both)]))),
        "control_logit_error": float(np.max(cat([
            logit_errors(s, b[2], lo["at"][b[2], 0])
            for s, b, lo in zip(stats, both, low)]))),
        "program_gap": float(np.max(cat([gaps(s, b[2], 0)
                                         for s, b in zip(stats, both)]))),
        "program_logit_error": float(np.max(cat([
            logit_errors(s, b[2], it.logits)
            for s, b, it in zip(stats, both, items)]))),
        "tokens": int(sum(len(b[2]) for b in both)),
    }


def checks(widest: float, limit: float, logit_err: float, err_limit: float,
           compared: int, regen_changed: int) -> Dict[str, Dict[str, float]]:
    """Each number compared, beside its limit (tokens_compared: at least)."""
    return {
        "widest_gap": {"value": widest, "limit": limit},
        "logit_error": {"value": logit_err, "limit": err_limit},
        "regenerated_changed": {"value": regen_changed, "limit": 0},
        "tokens_compared": {"value": compared, "limit": 1},
    }


def control_checks(ctl: Dict[str, float], limits: Dict[str, float]
                   ) -> Dict[str, Dict[str, float]]:
    """The control's readings (`control`) as the comparison's checks: the
    token the control puts first and its logit of the served token, held
    to the configuration's limits exactly as the program's are."""
    return checks(ctl["control_gap"], float(limits["widest_gap"]),
                  ctl["control_logit_error"], float(limits["logit_error"]),
                  ctl["tokens"], 0)


def passed(c: Dict[str, Dict[str, float]]) -> bool:
    def under(name):
        v = c[name]["value"]
        return bool(np.isfinite(v)) and v <= c[name]["limit"]
    return (under("widest_gap") and under("logit_error")
            and under("regenerated_changed")
            and c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"])
