"""Device milliseconds the block-list attention kernel takes in one
decode step: self time on device 0 of the
``rtpu_sparse_decode_attention`` custom calls
(``ops/sparse_attention.py`` names its ``pl.pallas_call``; the step
calls it once a sparse layer for all slots) over the ``decode_chunk``
program's executions in the trace x ``decode_chunk`` steps each. The
kernel alone: the selection before it (scores over the compressed keys,
the pooling, the top-k) is XLA fusions with names of their own and lies
in `decode_step_ms`."""

import re

KERNEL = re.compile(
    r"rtpu_sparse_decode_attention\.?\d* custom-call .*tpu_custom_call$")


def kernel_seconds(run, kernel=KERNEL):
    """(seconds, calls) of a named kernel in the traced stretch."""
    t = run.get("trace") or {}
    names = [n for n in t.get("op_self_s", {}) if kernel.match(n)]
    return (sum(t["op_self_s"][n] for n in names),
            sum(t.get("op_count", {}).get(n, 0) for n in names))


def ms_per_step(run, kernel):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    seconds, _ = kernel_seconds(run, kernel)
    if not runs or not seconds:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3


def read(run):
    return ms_per_step(run, KERNEL)
