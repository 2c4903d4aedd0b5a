"""Device milliseconds a decode step spends MOVING a block's weights
where it should only read them: self time on device 0, in the traced
stretch, of the ``copy``, ``dynamic-slice`` and
``dynamic-update-slice`` operations whose result is an array of a
block's matrix shapes (`opcount_looped.weight_shapes`, alone, a layer's
``[1, ..]`` or the stack's ``[L, ..]``), over the ``decode_chunk``
program's executions x ``decode_chunk`` steps each. A slice that a
product reads where it lies is part of the product's fusion and is not
such an operation; one that stands alone copies the matrix (PERF.md
section 6, PR 33: 6 GB a step). 0 is the design: a loop nested round
the scan over layers must read the weights once a pass and copy them
never. Only of a program that counts its passes (``loop_passes``)."""

import re

from benchmark.harness import counters, opcount_looped

MOVE = re.compile(r"\S+ (copy|dynamic-slice|dynamic-update-slice) "
                  r"bf16\[(?:\d+,)?(\d+),(\d+)\]$")


def read(run):
    t = run.get("trace") or {}
    runs = t.get("program_s", {}).get("decode_chunk")
    if not runs or counters.delta(run, "loop_passes") is None:
        return None
    shapes = opcount_looped.weight_shapes(run["config"])
    seconds = 0.0
    for name, s in t.get("op_self_s", {}).items():
        m = MOVE.match(name)
        if m and (int(m.group(2)), int(m.group(3))) in shapes:
            seconds += s
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3
