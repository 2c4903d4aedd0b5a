"""Bytes and operations that a LOOPED decoder's decode step NEEDS, from
shapes and counters alone: the benchmark's own arithmetic, which counts
the WORK whatever implements it. Takes the configuration file's dict,
with Hugging Face key names.
"""

from __future__ import annotations

BF16 = 2


def block_bytes(c: dict) -> int:
    """One block's matrices in bf16: q, k, v, o and the SwiGLU's three
    (102,760,448 B at the published sizes; the four norms' gains, 32
    KB, are left out: a little low, never high)."""
    d, hd = c["hidden_size"], c["head_dim"]
    attn = 2 * d * (c["num_attention_heads"] + c["num_key_value_heads"]) * hd
    return (attn + 3 * d * c["intermediate_size"]) * BF16


def head_bytes(c: dict) -> int:
    """The untied output head (201,326,592 B); the embedding is a
    gather of one row a slot."""
    return c["hidden_size"] * c["vocab_size"] * BF16


def row_bytes(c: dict) -> int:
    """One position's K and V rows of ONE (pass, layer) cache entry, all
    heads (8,192 B)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def weight_shapes(c: dict) -> set:
    """(rows, columns) of a block's matrices, as stored and
    transposed."""
    d, hd, f = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    pairs = {(c["num_attention_heads"] * hd, d),
             (c["num_key_value_heads"] * hd, d), (d, f)}
    return pairs | {(b, a) for a, b in pairs}


def step_cost(c: dict, layer_steps: float, rows_streamed: float,
              slot_steps: float = 0.0) -> dict:
    """ONE decode step that ran ``layer_steps`` block applications
    (passes x layers: COUNTED by the program, ``loop_layer_steps``) and
    whose attention kernel fetched ``rows_streamed`` rows of ONE cache
    entry (``decode_attn_rows_streamed``), for ``slot_steps`` live
    slots. The weights cannot stay in fast memory between passes (4.93
    GB a pass) and a token's pass u + 1 needs its pass u, so every
    application reads its block: ``layer_steps x block_bytes`` is a
    true floor. The head once; the rows of every entry once. The new
    rows written, the norms' gains and the embedding's rows are left
    out. Operations: 2 a matrix parameter a live slot, far under the
    chip's 240 a byte: memory-bound."""
    params = block_bytes(c) // BF16
    rows = rows_streamed * layer_steps * row_bytes(c)
    return {"bytes": layer_steps * block_bytes(c) + head_bytes(c) + rows,
            "rows_bytes": rows,
            "flops": 2.0 * slot_steps * (layer_steps * params
                                         + head_bytes(c) // BF16)}
