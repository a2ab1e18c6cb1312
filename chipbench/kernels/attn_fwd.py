"""The Pallas flash-attention forward (``kernels/flash_attention.py``).

One call covers one [rows, heads, seq, head size] causal self-attention.  The
algorithm needs both products, Q K^T and P V, over the causal triangle only,
and must read Q, K and V and write O once, in bfloat16.
"""
from __future__ import annotations


def shapes(conf: dict, cell: dict) -> dict:
    return dict(rows=cell["mb_rows"], heads=conf["n_heads"], seq=cell["seq"],
                head=conf["d_head"])


def flops(rows: int, heads: int, seq: int, head: int) -> float:
    pairs = seq * (seq + 1) / 2
    return 4.0 * rows * heads * pairs * head


def bytes_moved(rows: int, heads: int, seq: int, head: int) -> float:
    return 4.0 * rows * heads * seq * head * 2


def match(op: str) -> bool:
    """The kernel's op in a device trace: the jitted wrapper's name."""
    return op.startswith("flash_attention_fwd")
