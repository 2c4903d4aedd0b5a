"""The lightning state step's share of its roofline: the least time the
chip could take for the states stepped in the traced stretch over the
kernel's device time there. REQUIRED bytes
(`opcount_sala.lightning_decode_cost`): each stepped state read once
and written once at its float32 size (2 x 2,097,152 B a lightning layer
a live slot), plus q, k, v, the decay and the output. The states are
COUNTED (``lightning_state_steps`` grows by live slots x lightning
layers a step): a slot that is idle or frozen, whose state the kernel
still moves untouched, lowers the share and is not asked for."""

from benchmark.harness import opcount_sala
from benchmark.metrics import lightning_decode_ms_per_step as _ms
from benchmark.metrics import sparse_decode_attn_roofline as _share


def read(run):
    return _share.share(run, _ms.KERNEL, "lightning_state_steps",
                        opcount_sala.lightning_decode_cost)
