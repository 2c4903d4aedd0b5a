"""Device milliseconds of one decode step OUTSIDE its two named parts:
`decode_step_ms` less the grouped expert products
(`top1_moe_stream_roofline.product_seconds`) less the
``rtpu_decode_attention`` kernel (`decode_attn_ms_per_step`), a step.
It is where CCA's mixing (the two convolutions, the means, the norms
and the rotation), the projections, the router MLP, the sort around
the grouped product and the 262,272-row head are: XLA fusions, which
carry no name of their own in a trace (PERF.md section 7). Only where
the step has grouped products of ONE expert a token (this family's
key ``num_experts``): elsewhere it reads nothing."""

from benchmark.metrics import decode_attn_ms_per_step as _attn
from benchmark.metrics import decode_step_ms as _step
from benchmark.metrics import top1_moe_stream_roofline as _moe


def read(run):
    t = run.get("trace") or {}
    runs = t.get("program_s", {}).get("decode_chunk")
    if not runs or "num_experts" not in run["config"]:
        return None
    step = _step.read(run)
    products = _moe.product_seconds(run)
    if step is None or not products:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    products_ms = products / (len(runs) * chunk) * 1e3
    return step - products_ms - (_attn.read(run) or 0.0)
