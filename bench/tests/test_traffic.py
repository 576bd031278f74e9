"""The one traffic generator is a function of the seed alone."""
import numpy as np
import pytest

from bench import traffic

SEEDS = (0, 7, 2**31 + 12345, 2**40 + 3)


@pytest.mark.parametrize("name", ["cold"])
@pytest.mark.parametrize("seed", SEEDS)
def test_closed_loop_is_determined_by_the_seed(name, seed):
    mix = traffic.load_mix(name)

    def first(s, n=5):
        gen = traffic.closed_loop(mix, np.random.default_rng([s, 2]), 45)
        return [next(gen) for _ in range(n)]
    a, b, c = first(seed), first(seed), first(seed + 1)
    assert all(np.array_equal(x.factors, y.factors) for x, y in zip(a, b))
    assert not all(np.array_equal(x.factors, y.factors)
                   for x, y in zip(a, c))
    lo, hi = mix["scale"]
    for r in a:
        assert r.factors.shape == (45,)
        assert np.all((lo <= r.factors) & (r.factors <= hi))


def test_only_a_closed_loop_is_generated(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "open.json").write_text('{"loop": "open"}')
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="closed"):
        traffic.load_mix("open")
