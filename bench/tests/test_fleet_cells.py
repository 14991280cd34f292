"""The benchmark's copies of the fleet grids match benchmarks/fleet.py."""
import pytest

from bench import fleet_cells

fleet = pytest.importorskip("benchmarks.fleet")


def test_million_peer_cells_match():
    assert fleet_cells.million_peer_cells(16) == fleet.million_peer_cells(16)


@pytest.mark.parametrize("skew", [0, 4])
def test_pm_cells_match(skew):
    assert fleet_cells.pm_cells(32, skew=skew) == fleet._pm_cells(32, skew=skew)


def test_sweep_seeds_are_fresh_and_in_range():
    a = fleet_cells.sweep_seeds(2 ** 30 - 5, 1, 10)
    b = fleet_cells.sweep_seeds(2 ** 30 - 5, 2, 10)
    assert not set(a) & set(b)
    assert all(0 <= s < 2 ** 31 for s in a + b)
