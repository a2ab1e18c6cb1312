"""The Pallas SSD forward, Mamba-2's chunked scan (``kernels/ssd_scan.py``).

One call covers one [rows, heads, seq, head size] scan with state size
``state``, in chunks of ``chunk`` positions, and one group: B and C are shared
by every head.  The algorithm needs, in each chunk, C B^T over the causal
pairs once, and for each head the masked (C B^T o decay) (dt x) over the same
pairs, C state^T for what enters from earlier chunks, and the state update
x^T B.  It must read x, dt, B and C and write y once: x, B, C and y in
bfloat16, dt in float32.
"""
from __future__ import annotations


def shapes(conf: dict, cell: dict) -> dict:
    return dict(rows=cell["mb_rows"], heads=conf["ssm_heads"],
                seq=cell["seq"], head=conf["ssm_head_dim"],
                state=conf["d_state"], chunk=conf["chunk"])


def flops(rows: int, heads: int, seq: int, head: int, state: int,
          chunk: int) -> float:
    chunks = -(-seq // chunk)
    pairs = chunk * (chunk + 1) / 2
    per_head = 2.0 * pairs * head + 4.0 * chunk * state * head
    return rows * chunks * (2.0 * pairs * state + heads * per_head)


def bytes_moved(rows: int, heads: int, seq: int, head: int, state: int,
                chunk: int) -> float:
    x_and_y = 2 * rows * seq * heads * head * 2
    return x_and_y + rows * seq * heads * 4 + 2 * rows * seq * state * 2


def match(op: str) -> bool:
    """The kernel's op in a device trace: the ``pallas_call``'s name."""
    return op.startswith("ssd_scan")
