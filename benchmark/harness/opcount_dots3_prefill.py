"""Operations that the dots3-note family's PREFILL attention needs, from
the configuration and a counter alone (`opcount_dots3` has the decode
step's kernels; the benchmark's own arithmetic, which a later PR of the
program may not change). Takes the configuration file's dict, with
Hugging Face key names.
"""

from __future__ import annotations


def dsa_prefill_attention_cost(c: dict, chosen_rows: float) -> dict:
    """The full layers' prefill attention over ``chosen_rows`` (query,
    row) pairs (the program's counter ``dsa_prefill_rows_attended``:
    the rows each real query of a chunk KEPT, at most ``index_topk``,
    summed over queries and full layers): all 128 heads' score product
    over the head's key (128 + 64) and value product (128) for each
    pair. The expansion of the latent rows to keys and values is left
    out (it depends on how many rows a kernel expands, not on what the
    queries chose), and so are the bytes: a chunk's queries share their
    rows, so a pair is 81,920 operations over bytes that 2,048 queries
    divide. Low, never high."""
    h = c["num_attention_heads"]
    per_pair = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return {"bytes": 0.0, "flops": 2.0 * chosen_rows * h * per_pair}
