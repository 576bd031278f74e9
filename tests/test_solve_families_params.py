"""The checks of ``test_solve_families`` in the parametric families whose
channels carry their own shape parameters: drift rates and failure
probabilities."""
import pytest

from test_solve_families import SHAPES, check_greedy, check_joint

FAMILIES_HERE = ("drift", "defective")


@pytest.mark.parametrize("lam_var", [0.0, 0.5])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_joint_decision_invariants(family, shape, lam_var):
    check_joint(family, shape, lam_var)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("family", FAMILIES_HERE)
def test_greedy_decision_invariants(family, shape):
    check_greedy(family, shape)
