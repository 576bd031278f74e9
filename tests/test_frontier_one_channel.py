"""The launch the workflow solver makes for its single-channel stages, in
every family and launch mode: rows of one channel each, every row its own
stage's statistics, split [1]."""
import numpy as np
import pytest

from frontier_cases import close, oracle, run, stacked

FAMILIES = ("normal", "lognormal", "drift", "empirical", "defective")
MODES = ("fwd", "grad", "pgrad")


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("dist_id", FAMILIES)
def test_one_channel_stacked_launch(dist_id, mode):
    """The solver's launch for its single-channel stages: rows of one
    channel each, every row its own stage's statistics, split [1]. The
    kernel (interpret mode) gives the oracle's moments and adjoints."""
    # spreads of at most a fifth of the mean: no mass below t = 0
    W, mus, sgs, extra = stacked(dist_id, 6, 1, seed=len(dist_id),
                                  cov=(0.05, 0.2))
    W = np.ones_like(W)
    got = run(mode, "pallas_interpret", W, mus, sgs, dist_id, extra, 128)
    close(got, oracle(mode, W, mus, sgs, dist_id, extra, 128))
    if dist_id == "normal":
        # one channel under its whole split: the channel's own moments,
        # the variance to the resolution of a 128-point grid
        np.testing.assert_allclose(np.asarray(got[0]), mus[:, 0], rtol=1e-4)
        np.testing.assert_allclose(np.asarray(got[1]), sgs[:, 0] ** 2,
                                   rtol=1e-2)
