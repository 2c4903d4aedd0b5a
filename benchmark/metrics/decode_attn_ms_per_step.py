"""Device milliseconds the decode-attention kernel takes in one decode
step: self time on device 0 of the kernel's ``tpu_custom_call``
operations over the ``decode_chunk`` program's executions in the trace
x ``decode_chunk`` steps each. The kernel alone: the fusions that stage
each slot's K/V into fast memory keep names of their own. Not a
roofline share (PERF.md section 3 says why none is given).

Which operations are the kernel's. ``ops/decode_attention.py`` passes
``name="rtpu_decode_attention"`` to its ``pl.pallas_call``, and XLA
names the instruction for the innermost scope of its call site: called
directly it is ``rtpu_decode_attention.N``. The engine ``vmap``s the
decode step over slots, JAX batches a kernel with a scalar-prefetch
argument by a loop of its own over the batch, and the innermost scope
is then that loop's body: ``closed_call.N`` (v5e trace, PR 25). The
kernel's name survives there only in the instruction's
``kernel_metadata`` (``{"kernel": "rtpu_decode_attention"}``), which
`trace_reduce.short_name` does not keep. So the reader takes both
names; the serving programs hold no other Pallas kernel."""

import re

KERNEL = re.compile(
    r"(rtpu_decode_attention|closed_call)\.\d+ custom-call .*tpu_custom_call$")


def read(run):
    t = run.get("trace") or {}
    runs = t.get("program_s", {}).get("decode_chunk")
    seconds = sum(s for name, s in t.get("op_self_s", {}).items()
                  if KERNEL.match(name))
    if not runs or not seconds:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3
