"""The block-list attention kernel's share of its roofline: the least
time the chip could take for the rows that decode queries SELECTED in
the traced stretch over the kernel's device time there. REQUIRED bytes
(`opcount_sala.sparse_decode_cost`): each selected row's K and V once
(1,024 B a row a sparse layer). The rows are COUNTED by the program
(``sparse_rows_selected``: the listed blocks' rows under the slot's
length), not inferred from what the kernel fetched: a kernel that
fetches whole blocks past a slot's length, or blocks it then masks,
shows as a LOW share, and none can pass 100 %."""

from benchmark.harness import opcount, opcount_sala
from benchmark.metrics import sparse_decode_attn_ms_per_step as _ms


def traced_delta(run, counter):
    """The counter's growth over the traced stretch, or None."""
    c = run.get("counters") or {}
    if "trace_end" not in c or counter not in c["trace_end"]:
        return None
    return c["trace_end"][counter] - c["trace_start"].get(counter, 0)


def share(run, kernel, counter, cost):
    if not run.get("trace") or run["peaks"] is None:
        return None
    seconds, calls = _ms.kernel_seconds(run, kernel)
    counted = traced_delta(run, counter)
    if not calls or not seconds or not counted:
        return None
    return opcount.roofline_seconds(cost(run["config"], counted),
                                    run["peaks"]) / seconds * 100


def read(run):
    return share(run, _ms.KERNEL, "sparse_rows_selected",
                 opcount_sala.sparse_decode_cost)
