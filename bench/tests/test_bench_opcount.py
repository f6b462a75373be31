"""The per-step-pass operation count against counts made by hand."""
import pytest

from bench import opcount

# Hand counts, item by item of the derivation in bench/opcount.py.
#   nx=1, ny=1 (m=2), taylor: linearization 6, filtering element 94,
#   two filtering combines 2 x 101/3, smoothing element 50/3, two
#   smoothing combines 2 x 10, cost 62/3: 674/3.
#   nx=2, ny=1 (m=3), taylor: 15 + 387 + 2 x 574/3 + 292/3 + 2 x 62 + 47
#   = 1053.
#   nx=1, ny=1, slr: linearization 2 x 33 + 2 = 68, cost 68 + 44/3;
#   the rest as for taylor: 1046/3.
HAND = [
    (1, 1, "taylor", 674 / 3),
    (2, 1, "taylor", 1053.0),
    (1, 1, "slr", 1046 / 3),
]


@pytest.mark.parametrize("nx,ny,kind,want", HAND)
def test_per_step_pass_matches_hand_count(nx, ny, kind, want):
    assert opcount.per_step_pass(nx, ny, kind) == pytest.approx(want,
                                                                rel=1e-12)


@pytest.mark.parametrize("kind", ["taylor", "slr"])
def test_count_grows_with_the_state(kind):
    counts = [opcount.per_step_pass(nx, 2, kind) for nx in (1, 2, 5, 8)]
    assert counts == sorted(counts) and counts[0] > 0
