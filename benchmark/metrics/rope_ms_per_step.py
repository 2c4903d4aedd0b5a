"""Device milliseconds the rotary embedding's kernel takes in one train
step: self time on device 0 of the trace's ``rtpu_fused_qk_rope`` custom
calls (``ops/fused.py`` names its ``pl.pallas_call``; a step calls it
once a layer forward, q and k together, and once a layer backward on
their cotangents) over the steps of the traced stretch. A program
without the kernel (the plain-XLA rope before it is fusions that carry
no name) reads nothing, so a number here also says that the kernel
engaged."""

import re

from benchmark.metrics.sparse_decode_attn_ms_per_step import kernel_seconds

KERNEL = re.compile(r"rtpu_fused_qk_rope\.?\d* custom-call .*tpu_custom_call$")


def read(run):
    seconds, calls = kernel_seconds(run, KERNEL)
    steps = len(run.get("steps") or ())
    if not calls or not seconds or not steps:
        return None
    return seconds / steps * 1e3
