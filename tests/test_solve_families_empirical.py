"""The checks of ``test_solve_families`` in the empirical family: every
channel a Gaussian mixture fitted to its own samples."""
import pytest

from test_solve_families import SHAPES, check_greedy, check_joint

FAMILIES_HERE = ("empirical",)


@pytest.mark.parametrize("lam_var", [0.0, 0.5])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_joint_decision_invariants(family, shape, lam_var):
    check_joint(family, shape, lam_var)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_greedy_decision_invariants(family, shape):
    check_greedy(family, shape)
