"""Device milliseconds of the expert products in one decode step: self
time on device 0 of the ``decode_chunk`` program's grouped matrix
products over its executions in the trace x ``decode_chunk`` steps.

Which operations those are. ``models/glm_moe_lite.py`` multiplies the
(token, expert) pairs, sorted by expert, with ``jax.lax.ragged_dot``,
for which the chip's compiler has a kernel of its own: the trace shows
three ``ragged-dot-none[.N]`` custom calls an expert layer (gate, up,
down). The prefill program holds the same names; the step's are told
apart by their shape, whose rows are slots x experts a token (128 at
the cell's sizes; a prefill bucket's are bucket x experts a token). The
routing around them (a sigmoid, two small sorts, gathers) is spread
over fusions that carry no name of their own and is not in this
number; `decode_step_ms` holds it."""

import re

KERNEL = re.compile(r"ragged-dot-none\.?\d* custom-call [a-z0-9]+\[(\d+),\d+\] "
                    r".*tpu_custom_call$")


def read(run):
    t = run.get("trace") or {}
    runs = t.get("program_s", {}).get("decode_chunk")
    eng = run["config"]["driver_args"]["engine"]
    rows = eng["max_batch"] * run["config"].get("num_experts_per_tok", 0)
    seconds = sum(s for name, s in t.get("op_self_s", {}).items()
                  if (m := KERNEL.match(name)) and int(m.group(1)) == rows)
    if not runs or not seconds:
        return None
    return seconds / (len(runs) * eng["decode_chunk"]) * 1e3
