"""``profile["launches"]`` against the launches ``solve_dag`` really makes
(the recording is described in ``launch_records``): on XLA in the normal
and lognormal families, over four DAG shapes, and the Pallas kernel's own
layout (interpret mode). ``test_launch_accounting_params`` runs the
families with per-channel parameters."""
import pytest

from launch_records import check, check_xla_case, launches, solve  # noqa: F401
from workflow_shapes import SHAPES, make_dag


@pytest.mark.parametrize("family", ["normal", "lognormal"])
@pytest.mark.parametrize("shape", SHAPES)
def test_xla_profile_is_what_was_launched(shape, family, launches):
    check_xla_case(shape, family, launches)


@pytest.mark.parametrize("shape", SHAPES)
def test_pallas_profile_is_what_was_launched(shape, launches):
    dag = make_dag(shape, "normal", seed=SHAPES.index(shape))
    p = check(solve(dag, "pallas_interpret"), launches)
    if shape == "wide":
        # few rows of 16 channels: the kernel deals each row over slots
        assert min(e["pack"] for e in p["launches"]) > 1
