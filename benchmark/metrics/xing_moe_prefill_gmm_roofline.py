"""The four-stream routed family's prefill's grouped expert products'
share of their roofline, as `moe_prefill_gmm_roofline` makes it, with
this family's rule for telling a prefill from a step: the least time
the chip could take for the traced stretch's prefill products over
their device time there (`xing_moe_ms_per_step.product_seconds`).
REQUIRED (`opcount_grouped.grouped_prefill_cost`): each HELD expert that
a prefill touched streamed once a layer (22.0 MB), or the real held
pairs' three products at the chip's peak if that takes longer. The
touched experts are COUNTED by the program (``moe_prefill_expert_hits``;
the window's hits a dispatched chunk x the chunks whose products the
trace shows, as that reader has the reason), the pairs are the
stretch's real tokens x experts a token x expert layers x the share of
pairs this chip held over the window (Δ``moe_pairs_held`` /
Δ``moe_pairs_routed``). A prompt of 1,000 tokens puts 60 pairs on a
held expert (1.4 operations a byte of the chip's 240): streaming bounds
it, a product that streams an expert once a row tile reads LOW, and
none can pass 100 %."""

from benchmark.harness import counters, opcount, opcount_grouped
from benchmark.metrics import xing_moe_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    if not run.get("trace") or run.get("peaks") is None:
        return None
    c = run["config"]
    seconds, layers = _ms.product_seconds(run, step=False)
    traced = traced_delta(run, "prefill_chunk_tokens")
    hits = counters.delta(run, "moe_prefill_expert_hits")
    chunks = counters.delta(run, "prefill_chunks_dispatched")
    held = counters.delta(run, "moe_pairs_held")
    routed = counters.delta(run, "moe_pairs_routed")
    if not (seconds and layers and traced and hits and chunks and held
            and routed):
        return None
    moe_layers = opcount_grouped.expert_layers(c)
    pairs = traced * c["num_experts_per_tok"] * moe_layers * held / routed
    cost = opcount_grouped.grouped_prefill_cost(
        c, hits / chunks * layers / moe_layers, pairs)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
