"""The traffic generator: deterministic per seed, the same sizes for every
seed, and the clipped distributions and the grid hold."""
from collections import Counter

import numpy as np
import pytest

from chipbench import bench
from chipbench.traffic import generate as g

MIXES = sorted(p.stem for p in (bench.PKG / "traffic").glob("*.json"))
BIG_SEED = 2 ** 40 + 12345


def mix(name):
    return bench.load_json(bench.PKG / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = g.requests(mix(name), BIG_SEED, 50_000, 40)
    b = g.requests(mix(name), BIG_SEED, 50_000, 40)
    assert a == b
    # a slice asked for on its own is the same slice
    assert g.requests(mix(name), BIG_SEED, 50_000, 8, start_index=20) == a[20:28]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    t = mix(name)
    n = t.get("block", 16)
    a = g.requests(t, 1, 50_000, 4 * n)
    b = g.requests(t, 2, 50_000, 4 * n)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    for blk in range(4):
        ra, rb = a[blk * n:(blk + 1) * n], b[blk * n:(blk + 1) * n]
        assert Counter(len(r.prompt) for r in ra) == Counter(len(r.prompt) for r in rb)
        assert Counter(r.max_new for r in ra) == Counter(r.max_new for r in rb)
        if t["arrivals"] == "poisson":
            # every block of arrivals spans the same time
            span = g.block_sizes(t)[2].sum()
            assert min(r.due_s for r in ra) == pytest.approx(blk * span)


@pytest.mark.parametrize("name", MIXES)
def test_clipped_lengths_on_the_grid(name):
    t = mix(name)
    grid = g.grid(t["prompt"])
    assert len(grid) <= t["prompt"]["grid"]
    assert grid[0] >= t["prompt"]["min"] and grid[-1] >= t["prompt"]["max"] - 15
    assert all(x % 16 == 0 for x in grid)
    reqs = g.requests(t, 7, 1000, 64)
    for r in reqs:
        assert len(r.prompt) in grid
        assert t["output"]["min"] <= r.max_new <= t["output"]["max"]
        assert all(1 <= tok < 1000 for tok in r.prompt)
    # the block's quantiles follow the lognormal: its median quantile pair
    # straddles the stated median
    out = np.sort(g.block_sizes(t)[1])
    n = len(out)
    assert out[n // 2 - 1] <= t["output"]["median"] <= out[n // 2]


def test_poisson_rate_and_ndtri():
    t = mix("open-chat-4k")
    gaps = g.block_sizes(t)[2]
    # stratified exponential quantiles: mean gap within 3% of 1 / rate
    assert gaps.mean() * t["rate_per_s"] == pytest.approx(1.0, rel=0.03)
    p = np.array([1e-4, 0.01, 0.3, 0.5, 0.8, 0.999])
    want = np.array([-3.7190164854556804, -2.3263478740408408,
                     -0.5244005127080407, 0.0, 0.8416212335729143,
                     3.090232306167813])
    np.testing.assert_allclose(g._ndtri(p), want, atol=1e-8)
