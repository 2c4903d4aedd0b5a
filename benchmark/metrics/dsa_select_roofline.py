"""The selection kernel's share of its roofline: the least time the
chip could take to read the index keys of the rows that live slots'
queries could SEE in the traced stretch (256 B a row a full layer:
`opcount_dots3.dsa_select_cost`) over ``rtpu_dsa_select``'s device time
there. The rows are COUNTED by the program (``dsa_rows_visible``), not
inferred from what the kernel fetched: whole blocks read past a slot's
length, or the blocks of slots that are not live, show as a LOW share,
and none can pass 100 % (memory-bound: 64 operations a byte)."""

from benchmark.harness import opcount_dots3
from benchmark.metrics import dsa_select_ms_per_step as _ms
from benchmark.metrics import sparse_decode_attn_roofline as _roofline


def read(run):
    return _roofline.share(run, _ms.KERNEL, "dsa_rows_visible",
                           opcount_dots3.dsa_select_cost)
