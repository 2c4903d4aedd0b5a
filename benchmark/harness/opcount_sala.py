"""Operations and bytes that the MiniCPM-SALA family's two decode
kernels NEED, from shapes and counters alone: the family's copy of
`opcount` (the benchmark's own arithmetic; a later PR may change the
program's and may not change the yardstick). Both take the
configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

F32 = 4
BF16 = 2


def row_bytes(c: dict) -> int:
    """One row of one sparse layer as a query's selection reads it: K
    and V of every KV head (2 x 2 x 128 x 2 B = 1,024 B at the
    published sizes)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def sparse_decode_cost(c: dict, rows_selected: float) -> dict:
    """``rows_selected`` rows read by decode queries (a row of one
    sparse layer of one live slot is one; the program's counter
    ``sparse_rows_selected``: the rows of the blocks a query's
    selection lists, the rows past the slot's length left out, NOT what
    the kernel fetched): each row's K and V read once; a row's two
    products with every query head (2 x heads x head_dim each).
    Memory-bound: 16 query heads a KV head are 32 operations a byte."""
    h, d = c["num_attention_heads"], c["head_dim"]
    return {"bytes": rows_selected * row_bytes(c),
            "flops": rows_selected * 4.0 * h * d}


def state_bytes(c: dict) -> int:
    """One lightning layer's state of one slot: a float32 head_dim x
    head_dim matrix a head (2,097,152 B at the published sizes)."""
    return c["lightning_nh"] * c["lightning_head_dim"] ** 2 * F32


def lightning_decode_cost(c: dict, state_steps: float) -> dict:
    """``state_steps`` states stepped by one token each (one lightning
    layer of one live slot is one; the program's counter
    ``lightning_state_steps``): the state read once and written once;
    q, k, v and the decay read and the output written in float32. Per
    head the decay (D^2), the rank-one update (2 D^2) and ``S^T q``
    (2 D^2). Memory-bound: under one operation a byte."""
    h, d = c["lightning_nh"], c["lightning_head_dim"]
    io = h * (4 * d + 1) * F32
    return {"bytes": state_steps * (2 * state_bytes(c) + io),
            "flops": state_steps * 5.0 * h * d * d}
