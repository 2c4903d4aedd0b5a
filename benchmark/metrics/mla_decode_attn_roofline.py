"""The latent decode-attention kernel's share of its roofline: the
least time the chip could take for the traced stretch's calls over
their device time. REQUIRED bytes: every VALID latent row of a LIVE slot
once (1,152 B: 512 latent + 64 rotary values in bf16, whatever the
program pads them to) plus the queries and outputs. The rows are
COUNTED, not inferred: the step's counter ``mla_decode_rows`` is Σ
(lengths + 1) over all slots a step, the rows a layer's call is asked
to read; from it go the slots that were not live, which the engine
parks on their last row (``max_len`` rows each; ``decode_steps`` grows
by one for every live slot of every step, so the rest of the stretch's
slot-steps were not live. A slot that finished inside a chunk stays at
its own length for the chunk's remaining steps and is taken off as a
whole one: the required rows come out a little low, never high). A
kernel that reads every row of a slot, or the rows of idle slots, shows
as a LOW share; none can pass 100 % (memory-bound: 2 operations a byte
a head, and the program's rows are wider than what is required)."""

from benchmark.harness import opcount, opcount_routed
from benchmark.metrics import mla_decode_attn_ms_per_step as _ms


def read(run):
    t, c = run.get("trace"), run.get("counters") or {}
    if not t or run["peaks"] is None or "trace_end" not in c:
        return None
    seconds, calls = _ms.kernel_seconds(run)
    eng = run["config"]["driver_args"]["engine"]
    a, b = c["trace_start"], c["trace_end"]
    steps = ((b["decode_host_syncs"] - a["decode_host_syncs"])
             * eng["decode_chunk"])
    if not calls or not seconds or not steps or "mla_decode_rows" not in b:
        return None
    not_live = steps * eng["max_batch"] - (b["decode_steps"]
                                           - a["decode_steps"])
    rows = (b["mla_decode_rows"] - a.get("mla_decode_rows", 0)
            - not_live * eng["max_len"])
    cost = opcount_routed.mla_decode_attention_cost(
        run["config"], valid_rows=rows / steps, slots=eng["max_batch"])
    return calls * opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
