"""Of the experts a decode step's layers hold, the share that at least
one of the step's tokens chose: Δ``moe_expert_hits`` /
(Δ``moe_layer_steps`` x ``num_experts``) over the window. With ONE
expert a token and 64 tokens over 16 experts an even router touches 1 -
(15/16)^64 = 98 %: it is the share of the expert weights that a step
has to stream. (`moe_experts_touched_pct` is the GLM family's: its
reader takes the experts' number from that family's key.) A program
without the counters, or a configuration without the key, reads
nothing."""

from benchmark.harness import counters


def read(run):
    hits = counters.delta(run, "moe_expert_hits")
    layer_steps = counters.delta(run, "moe_layer_steps")
    experts = run["config"].get("num_experts")
    if hits is None or not layer_steps or not experts:
        return None
    return hits / (layer_steps * experts) * 100.0
