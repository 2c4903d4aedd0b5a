"""`test_manifest_open.py`'s second case, in a file of its own so that
the two run side by side."""

from tests.benchmark.test_manifest import (  # noqa: F401 — `copy` is a fixture
    _a_family_of_its_own, copy)
from tests.benchmark.test_manifest_open import the_suite_passes_on_a_copy


def test_the_suite_passes_on_a_copy_with_a_further_family(copy):  # noqa: F811
    the_suite_passes_on_a_copy(copy, _a_family_of_its_own, "stub.chat.steady")
