"""Device milliseconds the mHC mixes take in one decode step: self time
on device 0 of the ``rtpu_mhc_pre`` and ``rtpu_mhc_post`` custom calls
(``ops/mhc.py`` names its two ``pl.pallas_call``s; the trace shows
``rtpu_mhc_pre.N``) whose rows are the step's (the engine's slots: a
prefill bucket's are its tokens, never 32) over the ``decode_chunk``
program's executions in the trace x ``decode_chunk`` steps each. The
kernels alone: the sub-layer's own norm and the Sinkhorn error's
reduction are fusions and lie in `decode_step_ms`. A program without
the kernels reads nothing."""

import re

# The custom call's first result is [rows, ..]: the collapsed input of
# the pre kernel (a tuple: the maps follow), the streams of the post.
KERNEL = re.compile(r"rtpu_mhc_(?:pre|post)\.?\d* custom-call \(?f32\[(\d+),"
                    r".*tpu_custom_call$")


def kernel_seconds(run, step: bool):
    """(self seconds on device 0, calls) in the traced stretch of the
    mHC kernels whose rows are the decode step's (``step``) or are not
    (a prefill's)."""
    t = run.get("trace") or {}
    slots = run["config"]["driver_args"]["engine"]["max_batch"]
    seconds = calls = 0.0
    for name, s in t.get("op_self_s", {}).items():
        m = KERNEL.match(name)
        if m and (int(m.group(1)) == slots) == step:
            seconds += s
            calls += t.get("op_count", {}).get(name, 0)
    return seconds, calls


def read(run):
    runs = (run.get("trace") or {}).get("program_s", {}).get("decode_chunk")
    seconds, _ = kernel_seconds(run, step=True)
    if not runs or not seconds:
        return None
    chunk = run["config"]["driver_args"]["engine"]["decode_chunk"]
    return seconds / (len(runs) * chunk) * 1e3
