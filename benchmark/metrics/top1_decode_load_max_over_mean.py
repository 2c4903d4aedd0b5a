"""How uneven the routing of a decode step's tokens is: the largest
group's tokens over the mean group's, Δ``moe_decode_load_max`` /
(Δ``moe_layer_steps`` x slots x experts a token / experts) over the
window. ``moe_decode_load_max`` is the step's largest group summed over
its layers; every slot's token is routed (a slot that is not live too:
static shapes), so the mean group is ``max_batch`` x
``num_experts_per_tok`` / ``num_experts`` tokens. 1 is even; 64 tokens
thrown evenly at 16 experts read about 2. The grouped product computes
every pair whatever this reads (no capacity); a larger group is a
longer run of one expert's rows. A program without the counter reads
nothing."""

from benchmark.harness import counters


def read(run):
    most = counters.delta(run, "moe_decode_load_max")
    layer_steps = counters.delta(run, "moe_layer_steps")
    c = run["config"]
    experts = c.get("num_experts")
    if most is None or not layer_steps or not experts:
        return None
    mean = (c["driver_args"]["engine"]["max_batch"]
            * c["num_experts_per_tok"] / experts)
    return most / (layer_steps * mean)
