"""The rotary embedding kernel's share of its roofline: the least time
the chip could take for the traced ``rtpu_fused_qk_rope`` calls over
their device time on device 0. REQUIRED
(`opcount_rope.qk_rope_cost`): each call's q and k at their unpadded
bf16 size, read once and written once, from the shapes in the
configuration and the mix, a chip's even share of batch x heads, at
the chip's peak bytes/s: never from what the kernel did. Memory-bound:
a kernel that reads and writes nothing else and hides its arithmetic
reaches the share that a plain pass over HBM does (about 70 % on a
v5e), and none can pass 100."""

from benchmark.harness import opcount, opcount_rope
from benchmark.metrics.rope_ms_per_step import KERNEL
from benchmark.metrics.sparse_decode_attn_ms_per_step import kernel_seconds


def read(run):
    if not run.get("trace") or run.get("peaks") is None:
        return None
    seconds, calls = kernel_seconds(run, KERNEL)
    if not calls or not seconds:
        return None
    mix = run["traffic"]
    cost = opcount_rope.qk_rope_cost(run["config"], mix["batch"], mix["seq"])
    share = {k: v / run["chips"] for k, v in cost.items()}
    return calls * opcount.roofline_seconds(share, run["peaks"]) / seconds * 100
