"""How much of a decode step the mechanism is: the Mamba-2 state step's
device milliseconds a step (`mamba2_decode_ms_per_step`) over the whole
step's (`decode_step_ms`), in percent."""

from benchmark.metrics import decode_step_ms, mamba2_decode_ms_per_step


def read(run):
    kernel, step = (mamba2_decode_ms_per_step.read(run),
                    decode_step_ms.read(run))
    if not kernel or not step:
        return None
    return kernel / step * 100
