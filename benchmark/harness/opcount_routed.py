"""Operations and bytes that a routed, latent-attention decoder NEEDS,
from shapes alone: the GLM-4.7-Flash family's copy of `opcount` (the
benchmark's own arithmetic; a later PR may change the program's and may
not change the yardstick). All take the configuration file's dict, with
Hugging Face key names.
"""

from __future__ import annotations

BF16 = 2


def attention_params(c: dict) -> int:
    """One layer's MLA projections: q down and up, kv down and up, out
    (21.76 M at the published sizes)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_params(c: dict) -> int:
    """One expert's three matrices (9.44 M)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def active_params_per_token(c: dict) -> dict:
    """Parameters a token's matrix products touch, by kind of layer:
    84.7 M the dense one; 69.1 M an expert layer (attention, router,
    the shared expert and the chosen ones)."""
    d = c["hidden_size"]
    attn = attention_params(c)
    return {
        "dense_layer": attn + 3 * d * c["intermediate_size"],
        "expert_layer": (attn + d * c["n_routed_experts"]
                         + (c["n_shared_experts"] + c["num_experts_per_tok"])
                         * expert_params(c)),
        "head": d * c["vocab_size"],
    }


def latent_row_bytes(c: dict) -> int:
    """What a cached token a layer MEANS: the latent and the one shared
    rotary key (1,152 B). The program may store more (it pads the row to
    whole lanes); the roofline asks for this."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16


def mla_decode_attention_cost(c: dict, valid_rows: int, slots: int) -> dict:
    """One call of the latent decode-attention kernel (one layer, all
    slots): each valid latent row read once, the absorbed queries read
    and the latent outputs written; scores over the whole row, values
    over the latent."""
    h, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    row = rkv + c["qk_rope_head_dim"]
    byts = (valid_rows * latent_row_bytes(c)
            + slots * h * (row + rkv) * BF16)
    return {"bytes": byts, "flops": 2.0 * valid_rows * h * (row + rkv)}
