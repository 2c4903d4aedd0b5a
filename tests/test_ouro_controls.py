"""The controls of ``benchmark/degraded_looped.py`` that
``tests/benchmark/test_looped_controls.py`` leaves out (that directory's
tests run three times over), refused by `drivers/serve_looped.py`'s
check at the rehearsal's sizes; once."""

import pytest

from tests.benchmark import test_looped_controls as shared


@pytest.mark.parametrize("control",
                         sorted(set(shared.REFUSED) - set(shared.HERE)))
def test_a_control_is_refused(control, seed=1):
    shared.assert_refused(control, shared.bring_up(control, seed))
