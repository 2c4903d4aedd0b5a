"""The decode step's grouped expert products' share of their roofline
in the four-stream routed family, where a chip holds 8 of the router's
64 experts at two pairs each: the least time the chip could take to
stream the held experts that the traced stretch's steps TOUCHED over
the products' device time there (`xing_moe_ms_per_step.product_seconds`).
REQUIRED (`opcount_grouped.grouped_prefill_cost`, whose arithmetic is a
step's too): each touched held expert's three matrices once a
layer-step (22.0 MB an expert), the touched experts COUNTED by the
program (Δ``moe_expert_hits`` between the trace's two counter
snapshots); the pairs (which bound nothing here: 2 pairs an expert are
2 operations a byte) are the steps' slots x experts a token x the share
of the router's experts this chip holds. A product that streams an
expert nobody chose, or one twice, shows as a LOW share, and none can
pass 100 %."""

from benchmark.harness import opcount, opcount_grouped
from benchmark.metrics import xing_moe_ms_per_step as _ms


def read(run):
    c = run.get("counters") or {}
    if (not run.get("trace") or run.get("peaks") is None
            or "moe_expert_hits" not in c.get("trace_end", ())):
        return None
    seconds, _ = _ms.product_seconds(run, step=True)
    a, b = c["trace_start"], c["trace_end"]
    hits = b["moe_expert_hits"] - a.get("moe_expert_hits", 0)
    layer_steps = b["moe_layer_steps"] - a.get("moe_layer_steps", 0)
    if not seconds or not hits:
        return None
    cfg = run["config"]
    held = (cfg["n_routed_experts"]
            / cfg["reduced"]["n_routed_experts"]["source"])
    pairs = (layer_steps * cfg["driver_args"]["engine"]["max_batch"]
             * cfg["num_experts_per_tok"] * held)
    cost = opcount_grouped.grouped_prefill_cost(cfg, hits, pairs)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
