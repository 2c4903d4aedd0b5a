"""Bytes that rotating a layer's q and k REQUIRES, from the
configuration and the mix alone (the benchmark's own arithmetic, which
a later PR of the program may not change). Takes the configuration
file's dict, with Hugging Face key names.
"""

from __future__ import annotations

from benchmark.harness.opcount import BF16, _dims


def qk_rope_cost(c: dict, batch: int, seq: int) -> dict:
    """One rotation of one layer's q and k over full sequences, all
    heads of the whole batch (divide by the chips that share it evenly):
    q and k at their unpadded bf16 size, read once and written once. A
    forward call and a backward call (the cotangents rotated back) cost
    the same. A rotated element is two products and a sum in float32
    on the vector unit, which the chip's published peak (the matrix
    unit's) does not bound: bytes alone."""
    d, l, h, kh, hd, f, v = _dims(c)
    return {"bytes": 2.0 * batch * seq * (h + kh) * hd * BF16, "flops": 0.0}
