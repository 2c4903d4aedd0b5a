"""The full layers' decode attention's share of its roofline: the least
time the chip could take for the rows that decode queries CHOSE in the
traced stretch over ``rtpu_dsa_decode_attention``'s device time there.
REQUIRED (`opcount_dots3.dsa_decode_attention_cost`): each chosen row's
latent and rotary key once (1,152 B) and all 128 heads' products over
it, whichever bound is the larger (242 operations a byte: the chip's
ridge). The rows are COUNTED by the program (``dsa_rows_selected``: at
most ``index_topk`` a query), not inferred from what the kernel read: a
kernel that reads a slot's rows whole under the mask pays for every
visible row and shows as a LOW share (about ``dsa_rows_read_pct``'s
inverse), and none can pass 100 %."""

from benchmark.harness import opcount_dots3
from benchmark.metrics import dsa_decode_attn_ms_per_step as _ms
from benchmark.metrics import sparse_decode_attn_roofline as _roofline


def read(run):
    return _roofline.share(run, _ms.KERNEL, "dsa_rows_selected",
                           opcount_dots3.dsa_decode_attention_cost)
