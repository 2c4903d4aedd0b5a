"""Operations and bytes a program NEEDS, computed from shapes alone.

The benchmark's own copy (later PRs may change `LlamaConfig`'s
arithmetic and may not change the yardstick). All take the
configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations

BF16 = 2


def _dims(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d, c["num_hidden_layers"], h, c["num_key_value_heads"],
            c.get("head_dim", d // h), c["intermediate_size"],
            c["vocab_size"])


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the blocks' projections and the output head. The input embedding
    is a gather; tied, the one table IS the head."""
    d, l, h, kh, hd, f, v = _dims(c)
    per_layer = d * h * hd * 2 + d * kh * hd * 2 + 3 * d * f
    return l * per_layer + d * v


def param_count(c: dict) -> int:
    d, l, h, kh, hd, f, v = _dims(c)
    embed = v * d * (1 if c["tie_word_embeddings"] else 2)
    return matmul_params(c) - d * v + embed + (2 * l + 1) * d


def train_flops_per_token(c: dict, seq: int) -> float:
    """Required FLOPs of forward + backward per token: 6 per matmul
    parameter, plus causal attention's QK^T and PV (12·L·d·S counts the
    full square, as the usual MFU convention does). Recomputation under
    remat is NOT counted: it is the program's choice."""
    d, l = c["hidden_size"], c["num_hidden_layers"]
    return 6.0 * matmul_params(c) + 12.0 * l * d * seq


def flash_attention_cost(c: dict, batch: int, seq: int,
                         causal: bool = True) -> dict:
    """One layer's forward attention over full sequences, all heads of
    the whole batch (divide by the chips that share it evenly): QK^T
    and PV, half of the square under the causal mask; q, k, v read and
    o written once."""
    d, l, h, kh, hd, f, v = _dims(c)
    flops = 4.0 * batch * h * seq * seq * hd * (0.5 if causal else 1.0)
    byts = batch * seq * (2 * h + 2 * kh) * hd * BF16
    return {"bytes": byts, "flops": flops}


def roofline_seconds(cost: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    return max(cost["flops"] / peaks["flops"],
               cost["bytes"] / peaks["bytes_per_s"])
