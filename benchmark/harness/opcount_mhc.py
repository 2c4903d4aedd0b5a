"""Bytes and operations that the mixes of a multi-stream (mHC) residual
NEED, from shapes and counters alone: the benchmark's own arithmetic,
which counts the WORK whatever implements it (two kernels a sub-layer,
one kernel a boundary, or fusions). Takes the configuration file's
dict, with Hugging Face key names.
"""

from __future__ import annotations

F32 = 4


def maps_width(c: dict) -> int:
    """The outputs of ``Phi``: H_pre, H_post and the n x n H_res."""
    n = c["hc_mult"]
    return 2 * n + n * n


def stream_bytes(c: dict) -> int:
    """One token's streams as the configuration states them: float32,
    ``hc_mult`` x ``hidden_size`` (4 x 3584 x 4 = 57,344 B)."""
    return c["hc_mult"] * c["hidden_size"] * F32


def phi_bytes(c: dict) -> int:
    """One sub-layer's ``Phi``: float32 [nC, 2n + n^2] (1,376,256 B)."""
    return c["hc_mult"] * c["hidden_size"] * maps_width(c) * F32


def mix_cost(c: dict, row_sublayers: float, calls: float) -> dict:
    """``row_sublayers`` (token, sub-layer) pairs mixed in ``calls``
    sub-layer calls (a decode step's or a prefill chunk's; each has its
    own ``Phi``): the streams ``X`` read ONCE and written ONCE a pair,
    the sub-layer's output ``y`` read once (float32), ``Phi`` read once
    a call. The collapsed input ``x`` and the maps are left out (a
    one-pass program need not write the maps), so the required bytes
    come out a little low, never high. Operations: the product with
    ``Phi`` (2 nC M), the collapse (2 nC) and the write-back (2 n (n +
    1) C) a pair: 12 a byte of streams at the published sizes, far
    under the chip's 240: memory-bound."""
    n, d, m = c["hc_mult"], c["hidden_size"], maps_width(c)
    return {"bytes": (row_sublayers * (2 * stream_bytes(c) + d * F32)
                      + calls * phi_bytes(c)),
            "flops": row_sublayers * 2.0 * n * d * (m + 1 + n + 1)}
