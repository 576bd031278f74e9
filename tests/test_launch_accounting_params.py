"""``profile["launches"]`` against the launches ``solve_dag`` really makes,
on XLA in the families whose channels carry their own parameters: drift
rates, fitted mixtures and failure probabilities (the recording is
described in ``launch_records``)."""
import pytest

from launch_records import check_xla_case, launches  # noqa: F401
from workflow_shapes import SHAPES


@pytest.mark.parametrize("family", ["drift", "empirical", "defective"])
@pytest.mark.parametrize("shape", SHAPES)
def test_xla_profile_is_what_was_launched(shape, family, launches):
    check_xla_case(shape, family, launches)
