"""Device milliseconds of the HELD experts' grouped products in one
decode step of the four-stream routed family: self time on device 0 of
the ``decode_chunk`` program's grouped products over its executions in
the trace x ``decode_chunk`` steps. The step's 32 slots x 4 experts a
token are one whole tile of 128 rows over 8 held groups, which the rule
of ``ops/grouped_experts.py`` gives the repo's kernels
(``rtpu_grouped_swiglu`` + ``rtpu_grouped_matmul``, two a layer), so
`moe_ms_per_step`, which reads ``ragged-dot-none`` alone, finds nothing
here; under either name (`kimi_moe_ms_per_step.PRODUCT`) the step's are
told from a prefill's by their rows, slots x ``num_experts_per_tok``
(a bucket of 512 to 2,048 tokens is 2,048 to 8,192 rows). The routing
around them is spread over fusions with no name of their own and lies
in `decode_step_ms`. The manifest lists this family's cell alone."""

from benchmark.metrics.kimi_moe_ms_per_step import PRODUCT


def product_seconds(run, step: bool):
    """(self seconds on device 0, layers multiplied) in the traced
    stretch of the grouped products whose rows are the decode step's
    (``step``) or are not (a prefill's): the two kernels are a layer,
    or three ``ragged_dot`` calls."""
    t, c = run.get("trace") or {}, run["config"]
    rows = (c["driver_args"]["engine"]["max_batch"]
            * c["num_experts_per_tok"])
    seconds = layers = 0.0
    for name, s in t.get("op_self_s", {}).items():
        m = PRODUCT.match(name)
        if m and (int(m.group(1)) == rows) == step:
            seconds += s
            layers += (t.get("op_count", {}).get(name, 0)
                       / (3 if name.startswith("ragged") else 2))
    return seconds, layers


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    seconds, _ = product_seconds(run, step=True)
    if not runs or not seconds:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3
