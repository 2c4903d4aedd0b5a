"""How much of the device's time the mechanism is: the mHC kernels'
self time on device 0 in the traced stretch, a decode step's and a
prefill's together (`mhc_ms_per_step.kernel_seconds`), over the time
the device was busy there (``busy_s``), in percent."""

from benchmark.metrics import mhc_ms_per_step as _ms


def read(run):
    t = run.get("trace") or {}
    seconds = (_ms.kernel_seconds(run, step=True)[0]
               + _ms.kernel_seconds(run, step=False)[0])
    if not seconds or not t.get("busy_s"):
        return None
    return seconds / t["busy_s"] * 100
