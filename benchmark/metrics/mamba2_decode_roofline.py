"""The Mamba-2 state step's share of its roofline: the least time the
chip could take for the states stepped in the traced stretch over the
kernel's device time there. REQUIRED bytes
(`opcount_mamba2.mamba2_decode_cost`): each stepped state read once and
written once at its unpadded float32 size (2 x 2,097,152 B a Mamba
layer a live slot), plus x, B, C, dt, z and y. The states are COUNTED,
not inferred: the step's counter ``mamba2_slot_steps`` grows by (live
slots x Mamba layers) a step, so a slot that is idle or frozen, whose
state the kernel still moves untouched, lowers the share and is not
asked for. Memory-bound (under one operation a byte); a kernel that
moves padded lanes or the states of slots that are not live shows as a
LOW share, and none can pass 100 %."""

from benchmark.harness import opcount, opcount_mamba2
from benchmark.metrics import mamba2_decode_ms_per_step as _ms


def read(run):
    t, c = run.get("trace"), run.get("counters") or {}
    if not t or run["peaks"] is None or "trace_end" not in c:
        return None
    seconds, calls = _ms.kernel_seconds(run)
    a, b = c["trace_start"], c["trace_end"]
    if not calls or not seconds or "mamba2_slot_steps" not in b:
        return None
    stepped = b["mamba2_slot_steps"] - a.get("mamba2_slot_steps", 0)
    cost = opcount_mamba2.mamba2_decode_cost(run["config"], stepped)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
