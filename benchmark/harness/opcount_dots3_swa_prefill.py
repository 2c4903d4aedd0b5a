"""Operations that the dots3-note family's WINDOW layers' prefill
attention needs, from the configuration and a count of tokens alone
(`opcount_dots3_prefill` has the full layers'; the benchmark's own
arithmetic, which a later PR of the program may not change). Takes the
configuration file's dict, with Hugging Face key names.
"""

from __future__ import annotations


def swa_prefill_attention_cost(c: dict, tokens: float) -> dict:
    """The sliding layers' prefill attention over ``tokens`` real prompt
    tokens: each reads ``sliding_window_size`` rows (its own among
    them), all ``swa_num_attention_heads`` heads' score product over the
    head's key (192 + 64) and value product (128) for each pair, in each
    sliding layer the configuration holds. The expansion of the latent
    rows to keys and values is left out (it depends on how a program
    shares it among the queries), and so are the bytes: a block's
    queries share their rows."""
    layers = sum(kind == "sliding_attention"
                 for kind in c["layer_types"][:c["num_hidden_layers"]])
    per_pair = (c["swa_qk_nope_head_dim"] + c["swa_qk_rope_head_dim"]
                + c["swa_v_head_dim"])
    pairs = tokens * c["sliding_window_size"] * layers
    return {"bytes": 0.0,
            "flops": 2.0 * pairs * c["swa_num_attention_heads"] * per_pair}
