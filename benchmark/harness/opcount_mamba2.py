"""Operations and bytes that a Mamba-2 layer's state step NEEDS, from
shapes alone: the dense ``granitemoehybrid`` family's copy of `opcount`
(the benchmark's own arithmetic; a later PR may change the program's
and may not change the yardstick). All take the configuration file's
dict, with Hugging Face key names.
"""

from __future__ import annotations

F32 = 4


def state_bytes(c: dict) -> int:
    """One layer's state of one slot as the mathematics has it: a float32
    ``mamba_d_head x mamba_d_state`` matrix a head (2,097,152 B at the
    published sizes). The program may store more (lanes it pads); the
    roofline asks for this."""
    return (c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
            * F32)


def mamba2_decode_cost(c: dict, slot_steps: float) -> dict:
    """``slot_steps`` states stepped by one token each (one Mamba layer
    of one live slot is one): the state read once and written once; x,
    z and y (H P each), B and C (N each: one group, shared by the
    heads) and dt (H) in the float32 the step computes them in. Per
    state element the decay, the write ``dt x (x) B`` (2) and ``S C``
    (2): 5 H P N operations. Memory-bound: under one operation a
    byte."""
    h, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    io = (3 * h * p + 2 * n + h) * F32
    return {"bytes": slot_steps * (2 * state_bytes(c) + io),
            "flops": slot_steps * 5.0 * h * p * n}

