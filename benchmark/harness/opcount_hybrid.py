"""Operations and bytes that a gated delta-rule (linear-attention)
layer NEEDS, from shapes alone: the Olmo-Hybrid family's copy of
`opcount` (the benchmark's own arithmetic; a later PR may change the
program's and may not change the yardstick). All take the configuration
file's dict, with Hugging Face key names.
"""

from __future__ import annotations

F32 = 4


def state_bytes(c: dict) -> int:
    """One layer's state of one slot as the mathematics has it: a float32
    ``value_dim x key_dim`` matrix a head (2,211,840 B at the published
    sizes). The program may store more (lanes it pads); the roofline
    asks for this."""
    return (c["linear_num_value_heads"] * c["linear_value_head_dim"]
            * c["linear_key_head_dim"] * F32)


def gdn_decode_cost(c: dict, slot_steps: float) -> dict:
    """``slot_steps`` states stepped by one token each (one linear layer
    of one live slot is one): the state read once and written once;
    q, k, v, the decay and the write strength read and the output
    written, in the float32 the step computes them in. Per head the
    decay (dv dk), ``S k`` and ``S q`` (2 dv dk each) and the rank-one
    update (2 dv dk). Memory-bound: under one operation a byte."""
    h, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                 c["linear_value_head_dim"])
    io = h * (2 * dk + 2 * dv + 2) * F32
    return {"bytes": slot_steps * (2 * state_bytes(c) + io),
            "flops": slot_steps * 7.0 * h * dv * dk}
