"""The full layers' prefill attention's share of its roofline: the least
time the chip could take for the rows that the traced stretch's prefill
queries CHOSE over ``rtpu_dsa_prefill_attention``'s device time there.
REQUIRED (`opcount_dots3_prefill.dsa_prefill_attention_cost`): all 128
heads' score and value products over each (query, kept row) pair at the
chip's peak; the expansion of rows to keys and values is not counted.
The pairs are COUNTED by the program (``dsa_prefill_rows_attended``: at
most ``index_topk`` a real query a full layer), not inferred from what
the kernel read: a kernel that computes every visible row under the
mask pays for them all and shows as a LOW share (``index_topk`` over
the rows visible, times the kernel's share of the peak), and none can
pass 100 %.

The counter comes home with a prompt's LAST chunk (every chunk's
counters ride that one fetch), a whole prompt at a time, and five
seconds hold four or five prompts: taken between the trace's own two
snapshots it would swing by a prompt in five. So the pairs of the
stretch are the WINDOW's pairs a real prompt token
(Δ``dsa_prefill_rows_attended`` / Δ``prefill_tokens``, which land
together) times the real tokens of the chunks dispatched in the stretch
(`dsa_prefill_attn_ms_per_ktok`'s divisor)."""

from benchmark.harness import counters, opcount, opcount_dots3_prefill
from benchmark.metrics import dsa_prefill_attn_ms_per_ktok as _ms
from benchmark.metrics import sparse_decode_attn_ms_per_step as _kernel
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    if not run.get("trace") or run.get("peaks") is None:
        return None
    seconds, calls = _kernel.kernel_seconds(run, _ms.KERNEL)
    pairs = counters.delta(run, "dsa_prefill_rows_attended")
    tokens = counters.delta(run, "prefill_tokens")
    traced = traced_delta(run, "prefill_chunk_tokens")
    if not calls or not seconds or not pairs or not tokens or not traced:
        return None
    cost = opcount_dots3_prefill.dsa_prefill_attention_cost(
        run["config"], pairs / tokens * traced)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
