"""The rest of ``tests/benchmark/test_mamba2_controls.py``: the eight
controls of ``benchmark/degraded_mamba2.py`` that are about the layer's
mathematics and the weights' precision, and two more seeds of the sound
program, at the rehearsal sizes on the CPU. Here and not there because
that directory's tests run three times over."""

import pytest

from tests.benchmark.test_mamba2_controls import (
    REFUSED, STATE_CONTROLS, assert_refused, assert_sound, bring_up)


@pytest.mark.parametrize("seed", [1, 2147483659 + 46])
def test_the_sound_program_passes(seed):
    assert_sound(bring_up("none", seed))


@pytest.mark.parametrize("control", sorted(set(REFUSED) - set(STATE_CONTROLS)))
def test_a_control_is_refused(control, seed=1):
    assert_refused(control, bring_up(control, seed))
