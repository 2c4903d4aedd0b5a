"""Device milliseconds the Mamba-2 state step takes in one decode step:
self time on device 0 of the ``rtpu_mamba2_decode`` custom calls
(``ops/mamba2.py`` names its ``pl.pallas_call``; the step calls it
directly, once a Mamba layer for all slots, so the trace shows
``rtpu_mamba2_decode.N``) over the ``decode_chunk`` program's
executions in the trace x ``decode_chunk`` steps each. The kernel
alone: the convolution step, the gated norm and the projections around
it are fusions with names of their own and lie in `decode_step_ms`."""

import re

KERNEL = re.compile(r"rtpu_mamba2_decode\.?\d* custom-call .*tpu_custom_call$")


def kernel_seconds(run):
    """(seconds, calls) of the kernel in the traced stretch."""
    t = run.get("trace") or {}
    names = [n for n in t.get("op_self_s", {}) if KERNEL.match(n)]
    return (sum(t["op_self_s"][n] for n in names),
            sum(t.get("op_count", {}).get(n, 0) for n in names))


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    seconds, _ = kernel_seconds(run)
    if not runs or not seconds:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3
