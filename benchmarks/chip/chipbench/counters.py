"""Model FLOPs of the served work, from the counts that a configuration's
reference module computes for its own architecture (`hooks`).  Hand counts
at both configurations' widths are in tests/test_chipbench_counters.py.

Model FLOPs count what the model needs, not what the program pads: two
FLOPs per multiply-add of every projection for every token a pass
processes, attention over exactly the keys each query sees, and the LM
head only for rows whose logits are used (each decoded token, and the last
prompt token when a prefill completes).  What each of those is for one
architecture (which projections, how many experts a token meets, what one
key costs) is the reference module's to say; the arithmetic of passes and
chunks here is the same for every architecture.

A reference module's hooks, each of its configuration's ``arch``:

    matmul_flops_per_token(arch)   every layer's projections, one token
    attention_flops_per_key(arch)  one query against one key, every layer
    head_flops(arch)               the LM head, one row
    stage_decode_cost(arch, lo, hi, keys, first, last) -> (flops, bytes)
                                   one execution of the stage decode
                                   program over layers lo..hi-1 for a batch
                                   whose queries see `keys` keys each
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Sizes:
    """The per-token counts the window's arithmetic needs, whatever the
    architecture."""
    matmul_flops_per_token: int
    attention_flops_per_key: int
    head_flops: int

    @classmethod
    def from_reference(cls, ref, arch: dict) -> "Sizes":
        return cls(
            matmul_flops_per_token=int(ref.matmul_flops_per_token(arch)),
            attention_flops_per_key=int(ref.attention_flops_per_key(arch)),
            head_flops=int(ref.head_flops(arch)))


def decode_flops(s: Sizes, keys: int) -> int:
    """One decoded token whose query sees `keys` keys (itself included)."""
    return (s.matmul_flops_per_token + s.attention_flops_per_key * keys
            + s.head_flops)


def chunk_flops(s: Sizes, pos0: int, q: int, completes: bool) -> int:
    """A prefill chunk of `q` prompt tokens at positions pos0..pos0+q-1
    (query i sees pos0 + i + 1 keys); the head runs once if the chunk
    completes the prompt."""
    keys = q * pos0 + q * (q + 1) // 2
    return (q * s.matmul_flops_per_token + s.attention_flops_per_key * keys
            + (s.head_flops if completes else 0))
