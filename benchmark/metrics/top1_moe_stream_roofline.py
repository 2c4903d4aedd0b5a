"""The decode step's grouped expert products' share of their roofline:
the least time the chip could take to stream the experts that the
traced stretch's steps TOUCHED over the products' device time there.
REQUIRED bytes (`opcount_zaya.grouped_decode_cost`): each touched
expert's three matrices once a layer-step (25.2 MB an expert), the
touched experts COUNTED by the program (Δ``moe_expert_hits`` between
the trace's two counter snapshots), not inferred from what a kernel
fetched: a product that streams experts nobody chose, or an expert
twice, shows as a LOW share, and none can pass 100 % (memory-bound: 4
tokens an expert are 4 operations a byte). The products are found by
the names in `PRODUCTS` and told from a prefill's by their rows (slots
x experts a token, which no prefill bucket has): ``ragged-dot-none``
today, the chip compiler's own grouped matmul for ``lax.ragged_dot``;
a later kernel of the repo's own adds its name to be read against the
same bytes."""

import re

from benchmark.harness import opcount, opcount_zaya

PRODUCTS = ("ragged-dot-none",)


def product_seconds(run):
    """Self seconds on device 0 of the decode step's grouped products
    in the traced stretch."""
    t = run.get("trace") or {}
    c = run["config"]
    rows = (c["driver_args"]["engine"]["max_batch"]
            * c.get("num_experts_per_tok", 0))
    pattern = re.compile(
        r"(?:" + "|".join(map(re.escape, PRODUCTS)) + r")\.?\d* custom-call "
        r"[a-z0-9]+\[(\d+),\d+\] .*tpu_custom_call$")
    return sum(s for name, s in t.get("op_self_s", {}).items()
               if (m := pattern.match(name)) and int(m.group(1)) == rows)


def read(run):
    c = run.get("counters") or {}
    if (not run.get("trace") or run["peaks"] is None
            or "trace_end" not in c
            or "moe_expert_hits" not in c["trace_end"]
            or "num_experts" not in run["config"]):
        return None
    seconds = product_seconds(run)
    a, b = c["trace_start"], c["trace_end"]
    hits = b["moe_expert_hits"] - a.get("moe_expert_hits", 0)
    layer_steps = b["moe_layer_steps"] - a.get("moe_layer_steps", 0)
    if not seconds or not hits:
        return None
    eng = run["config"]["driver_args"]["engine"]
    cost = opcount_zaya.grouped_decode_cost(
        run["config"], hits,
        layer_steps * eng["max_batch"] * run["config"]["num_experts_per_tok"])
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
