"""How much of the device's busy time the two named mechanisms are: the
``rtpu_sparse_decode_attention`` and ``rtpu_lightning_decode`` kernels'
self time on device 0 over the traced stretch's busy time, in percent.
The kernels alone (what a trace can name): the selection's scores and
top-k, the prefill's masked attention and the prefill's chunked scan
are XLA fusions inside `decode_step_ms` and `prefill_ms_per_ktok`."""

from benchmark.metrics import lightning_decode_ms_per_step as _lightning
from benchmark.metrics import sparse_decode_attn_ms_per_step as _sparse


def read(run):
    t = run.get("trace") or {}
    seconds = (_sparse.kernel_seconds(run, _sparse.KERNEL)[0]
               + _sparse.kernel_seconds(run, _lightning.KERNEL)[0])
    if not seconds or not t.get("busy_s"):
        return None
    return seconds / t["busy_s"] * 100
