"""Of the experts a decode step's expert layers hold, the share that at
least one of the step's tokens chose: Δ``moe_expert_hits`` /
(Δ``moe_layer_steps`` x experts) over the window. It is the share of
the expert weights that a step has to stream. A program without the
counters (no routed family) reads nothing."""

from benchmark.harness import counters


def read(run):
    hits = counters.delta(run, "moe_expert_hits")
    layer_steps = counters.delta(run, "moe_layer_steps")
    if hits is None or not layer_steps:
        return None
    return hits / (layer_steps * run["config"]["n_routed_experts"]) * 100.0
