"""The forward flash-attention kernel's share of its roofline: the
least time the chip could take for its calls (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from shapes alone:
`opcount.flash_attention_cost`, each chip's even share of batch x
heads) over the device time of the trace's ``flash_attention*``
custom calls on device 0. Compute-bound at these shapes. The backward
kernels (``flash_mha_bwd_*``) are not in it."""

import re

from benchmark.harness import opcount

KERNEL = re.compile(r"flash_attention\S* custom-call .*tpu_custom_call$")


def read(run):
    t = run.get("trace")
    if not t or run["peaks"] is None:
        return None
    names = [n for n in t["op_self_s"] if KERNEL.match(n)]
    calls = sum(t["op_count"][n] for n in names)
    seconds = sum(t["op_self_s"][n] for n in names)
    if not calls or not seconds:
        return None
    mix = run["traffic"]
    cost = opcount.flash_attention_cost(run["config"], mix["batch"], mix["seq"])
    share = {k: v / run["chips"] for k, v in cost.items()}
    return calls * opcount.roofline_seconds(share, run["peaks"]) / seconds * 100.0
