"""Operations and bytes of the served work, computed from a configuration's
sizes: the model FLOPs behind `device.step_mfu` and the bytes behind the
`kv_pack_ragged` roofline.  Hand counts at both configurations' widths are
in tests/test_chipbench_counters.py.

Model FLOPs count what the model needs, not what the program pads: two
FLOPs per multiply-add of every projection (q, k, v, o and the MLP) for
every token a pass processes, causal attention over exactly the keys each
query sees (QK^T and PV), and the LM head only for rows whose logits are
used (each decoded token, and the last prompt token when a prefill
completes).  Embedding lookups, norms and softmax are not counted.
"""
from __future__ import annotations

from dataclasses import dataclass

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool
    itemsize: int

    @classmethod
    def from_arch(cls, arch: dict) -> "Sizes":
        head_dim = arch.get("head_dim") or arch["d_model"] // arch["num_heads"]
        return cls(layers=arch["num_layers"], d_model=arch["d_model"],
                   heads=arch["num_heads"], kv_heads=arch["num_kv_heads"],
                   head_dim=head_dim, d_ff=arch["d_ff"],
                   vocab=arch["vocab_size"],
                   gated_mlp=arch.get("activation", "silu") == "silu",
                   itemsize=_ITEMSIZE[arch.get("dtype", "bfloat16")])


def matmul_flops_per_token(s: Sizes) -> int:
    qd, kvd = s.heads * s.head_dim, s.kv_heads * s.head_dim
    per_layer = s.d_model * qd + 2 * s.d_model * kvd + qd * s.d_model
    per_layer += (3 if s.gated_mlp else 2) * s.d_model * s.d_ff
    return 2 * s.layers * per_layer


def attention_flops(s: Sizes, keys: int) -> int:
    """One query over `keys` keys, every layer: QK^T and PV."""
    return 4 * s.layers * s.heads * s.head_dim * keys


def head_flops(s: Sizes) -> int:
    return 2 * s.d_model * s.vocab


def decode_flops(s: Sizes, keys: int) -> int:
    """One decoded token whose query sees `keys` keys (itself included)."""
    return matmul_flops_per_token(s) + attention_flops(s, keys) + head_flops(s)


def chunk_flops(s: Sizes, pos0: int, q: int, completes: bool) -> int:
    """A prefill chunk of `q` prompt tokens at positions pos0..pos0+q-1
    (query i sees pos0 + i + 1 keys); the head runs once if the chunk
    completes the prompt."""
    keys = q * pos0 + q * (q + 1) // 2
    return (q * matmul_flops_per_token(s) + attention_flops(s, keys)
            + (head_flops(s) if completes else 0))


def kv_pack_ragged_bytes(s: Sizes, stage_layers: int, batch: int,
                         width: int) -> int:
    """One `kv_pack_ragged` call (K or V of one stage): it reads each
    sequence's `width`-token window of every layer and writes it packed."""
    window = stage_layers * batch * width * s.kv_heads * s.head_dim
    return 2 * window * s.itemsize
