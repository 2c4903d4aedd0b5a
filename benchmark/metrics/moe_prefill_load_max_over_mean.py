"""How uneven the routing of a prefill's tokens is: the fullest expert's
tokens over the mean expert's, Δ``moe_prefill_load_max`` /
Δ``moe_prefill_load_mean`` over the window (each summed over prefills
and expert layers; bucket padding is given to no expert). 1 is even; a
dropless layer computes every pair whatever this reads, a layer with a
capacity would drop above its factor."""

from benchmark.harness import counters


def read(run):
    most = counters.delta(run, "moe_prefill_load_max")
    mean = counters.delta(run, "moe_prefill_load_mean")
    if most is None or not mean:
        return None
    return most / mean
