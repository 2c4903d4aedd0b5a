"""Operations and bytes that the dots3-note family's own kernels NEED,
from shapes and counters alone: the family's copy of `opcount` (the
benchmark's own arithmetic; a later PR may change the program's and may
not change the yardstick). All take the configuration file's dict, with
Hugging Face key names.
"""

from __future__ import annotations

BF16 = 2


def latent_row_bytes(c: dict) -> int:
    """What a cached token of a FULL layer means: the latent and the one
    shared rotary key (512 + 64 values = 1,152 B). The program pads the
    row to 640 values; the roofline asks for this."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * BF16


def window_row_bytes(c: dict) -> int:
    """A cached token of a SLIDING layer (1,024 + 64 values = 2,176 B)."""
    return (c["swa_kv_lora_rank"] + c["swa_qk_rope_head_dim"]) * BF16


def index_key_bytes(c: dict) -> int:
    """The indexer's key of one token of one full layer (128 values =
    256 B)."""
    return c["index_head_dim"] * BF16


def dsa_decode_attention_cost(c: dict, chosen_rows: float) -> dict:
    """The full layers' decode attention over ``chosen_rows`` rows (the
    program's counter ``dsa_rows_selected``: rows in the chosen sets of
    live slots, summed over full layers and steps): each chosen latent
    row read ONCE, all 128 heads over it; scores over the whole row,
    values over the latent. The queries and outputs (some hundred
    kilobytes a call) are left out, so the required bytes come out a
    little low, never high. 242 operations a byte: at the chip's ridge,
    so the larger of the two bounds is taken."""
    h, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    row = rkv + c["qk_rope_head_dim"]
    return {"bytes": chosen_rows * latent_row_bytes(c),
            "flops": 2.0 * chosen_rows * h * (row + rkv)}


def dsa_select_cost(c: dict, visible_rows: float) -> dict:
    """Scoring and choosing over ``visible_rows`` rows (the counter
    ``dsa_rows_visible``: rows live slots' queries could read, summed
    over full layers and steps): each visible row's index key read ONCE
    and multiplied by the 64 index heads' queries; the searches over
    the scores touch no memory the roofline counts. The mask written
    (4 B a row of the slot as the program has it) is left out: low,
    never high. 64 operations a byte: memory-bound."""
    return {"bytes": visible_rows * index_key_bytes(c),
            "flops": 2.0 * visible_rows * c["index_n_heads"]
            * c["index_head_dim"]}
