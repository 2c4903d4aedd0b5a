"""Operations and bytes that the ZAYA1 family's expert layers NEED, from
shapes and counters alone: the family's copy of `opcount` (the
benchmark's own arithmetic; a later PR may change the program's and may
not change the yardstick). All take the configuration file's dict, with
Hugging Face key names.
"""

from __future__ import annotations

BF16 = 2


def expert_bytes(c: dict) -> int:
    """One expert's three matrices, gate, up and down (3 x 2048 x 2048
    x 2 B = 25,165,824 B at the published sizes)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"] * BF16


def row_bytes(c: dict) -> int:
    """One cached token of one layer: K and V of every KV head (2 x 2 x
    128 x 2 B = 1,024 B at the published sizes)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def tail_values(c: dict) -> int:
    """What a slot holds besides, a layer: the last token's
    pre-convolution latents, the first convolution's output for it and
    its shifted value half (1280 + 1280 + 128 = 2,688 values)."""
    channels = (c["num_attention_heads"]
                + c["num_key_value_heads"]) * c["head_dim"]
    return 2 * channels + c["num_key_value_heads"] * c["head_dim"] // 2


def grouped_decode_cost(c: dict, experts_hit: float, tokens: float) -> dict:
    """The grouped products of decode steps in which, summed over
    layer-steps, ``experts_hit`` experts were chosen by at least one
    token (the program's counter ``moe_expert_hits``) and ``tokens``
    (token, expert) pairs were multiplied: each touched expert's three
    matrices read ONCE a layer-step; each pair's three products (2 x d
    x f each). The activations (a few hundred kilobytes a layer-step
    beside 25 MB an expert) are left out, so the required bytes come
    out a little low, never high. Memory-bound at 4 tokens an expert:
    4 operations a byte."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    return {"bytes": experts_hit * expert_bytes(c),
            "flops": tokens * 3 * 2.0 * d * f}
