"""The mHC mixes' share of their roofline: the least time the chip
could take for the (token, sub-layer) pairs mixed in the traced stretch
over the kernels' device time there, decode steps and prefills
together. REQUIRED bytes (`opcount_mhc.mix_cost`): the four streams
read once and written once a pair (2 x 57,344 B), the sub-layer's
output read once, ``Phi`` once a sub-layer call. The pairs are COUNTED:
``mhc_step_rows`` grows by (live slots x sub-layers) a step and
``mhc_prefill_rows`` by (real tokens x sub-layers) a prefill, so a slot
that is idle and a bucket's padding, which the kernels mix all the
same, lower the share and are not asked for; the calls are the trace's
(each sub-layer call is one pre kernel). Two kernels a sub-layer read
the streams TWICE where one pass would do: such a program cannot pass
some 64 %, and none can pass 100 (memory-bound)."""

from benchmark.harness import opcount, opcount_mhc
from benchmark.metrics import mhc_ms_per_step as _ms
from benchmark.metrics.sparse_decode_attn_roofline import traced_delta


def read(run):
    if not run.get("trace") or run["peaks"] is None:
        return None
    step_s, step_calls = _ms.kernel_seconds(run, step=True)
    fill_s, fill_calls = _ms.kernel_seconds(run, step=False)
    pairs = ((traced_delta(run, "mhc_step_rows") or 0)
             + (traced_delta(run, "mhc_prefill_rows") or 0))
    seconds = step_s + fill_s
    if not seconds or not pairs:
        return None
    # A sub-layer call is a pre kernel and a post kernel.
    cost = opcount_mhc.mix_cost(run["config"], pairs,
                                (step_calls + fill_calls) / 2)
    return opcount.roofline_seconds(cost, run["peaks"]) / seconds * 100
