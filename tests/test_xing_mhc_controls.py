"""The rest of ``tests/benchmark/test_mhc_controls.py``: the controls of
``benchmark/degraded_mhc.py`` on the maps' mathematics, the streams'
ends, YaRN, the softmax scale and the held range, and two more seeds of
the sound program, at the rehearsal sizes on the CPU. Here and not there
because that directory's tests run three times over."""

import pytest

from tests.benchmark.test_mhc_controls import (
    HERE, REFUSED, assert_refused, assert_sound, bring_up)


@pytest.mark.parametrize("seed", [1, 2147483659 + 54])
def test_the_sound_program_passes(seed):
    assert_sound(bring_up("none", seed))


@pytest.mark.parametrize("control", sorted(set(REFUSED) - set(HERE)))
def test_a_control_is_refused(control, seed=1):
    assert_refused(control, bring_up(control, seed))


def test_the_write_back_probe_reads_zero_when_exact_and_2e3_in_bf16():
    """`serve_routed_mhc._handed_error` on numbers made here: two slots,
    three layers of two sub-layers, the streams handed on computed in
    float64 (exactly what the probe computes: it reads 0), then rounded
    to bf16 on their way (2^-9 of a value: 1e-3 and more, a hundred
    times the limit), then with H_post's factor left out of ONE
    sub-layer's write-back; slots past the first ``n`` are not read."""
    import numpy as np

    from benchmark.drivers import serve_routed_mhc as driver

    rng = np.random.default_rng(0)
    layers, slots, n, c = 3, 4, 4, 32
    y = rng.normal(size=(layers, 2, slots, c))
    maps = rng.uniform(0.1, 1.0, size=(layers, 2, slots, 2 * n + n * n))
    first = rng.normal(size=(slots, n * c))

    def run(hand_on, broken=None):
        x, after = first, np.zeros((layers, 2, slots, n * c))
        for layer in range(layers):
            for sub in range(2):
                m = maps[layer, sub]
                post = m[:, n:2 * n] * (0.5 if (layer, sub) == broken else 1)
                res = m[:, 2 * n:].reshape(slots, n, n)
                x = hand_on((np.einsum("bij,bjc->bic", res,
                                       x.reshape(slots, n, c))
                             + post[:, :, None] * y[layer, sub][:, None]
                             ).reshape(slots, n * c))
                after[layer, sub] = x
        return {"mhc_mixes": {"first": first, "y": y, "maps": maps,
                              "after": after}}

    def bf16(a):
        bits = a.astype(np.float32).view(np.uint32)
        return ((bits + 0x8000) & 0xFFFF0000).view(np.float32)

    assert driver._handed_error(run(lambda a: a), 2) < 1e-12
    assert 1e-3 < driver._handed_error(run(bf16), 2) < 4e-3
    assert driver._handed_error(run(lambda a: a, broken=(1, 1)), 2) > 0.01
    seen = run(lambda a: a)
    seen["mhc_mixes"]["after"][:, :, 2:] = 0.0
    assert driver._handed_error(seen, 2) < 1e-12
    assert driver.TOL_STREAMS_HANDED == 1e-5
