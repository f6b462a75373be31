"""The check's own arithmetic: lengths listed in a traffic mix, the
numbers compared, and their judgement against a cell's limits."""
import numpy as np
import pytest

from bench import check, traffic

LIMITS = check.load_limits("ct-ieks.fleet-pow2")


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_listed_lengths_come_in_equal_shares(seed):
    mix = {"lengths": [128, 256, 512]}
    got = traffic.lengths(mix, 300, seed)
    assert sorted(np.unique(got, return_counts=True)[1]) == [100] * 3
    assert np.array_equal(np.sort(got), np.sort(traffic.lengths(mix, 300, 1)))


def _numbers(converged, failed_over=0, gap=1e-6):
    lens = [128, 128, 256, 256, 512, 512]
    answers = [np.full((n + 1, 5), gap) for n in lens]
    ref = [np.zeros((n + 1, 5)) for n in lens]
    return check.numbers(answers, ref, np.asarray(converged), lens, 0,
                         failed_over)


@pytest.mark.parametrize("converged,fewest", [
    ([1, 1, 1, 1, 1, 1], 2),
    ([1, 0, 1, 1, 0, 1], 1),
    ([1, 1, 0, 0, 1, 1], 0),
])
def test_compared_fewest_counts_each_length_class(converged, fewest):
    values = _numbers([bool(c) for c in converged])
    assert values["compared_fewest"] == fewest
    assert check.judge(values, LIMITS)[0] == (fewest >= 1)


@pytest.mark.parametrize("failed_over,gap,correct", [
    (0, 1e-6, True),
    (1, 1e-6, False),
    (0, 0.5, False),
])
def test_failed_lanes_and_wide_gaps_are_not_correct(failed_over, gap,
                                                    correct):
    values = _numbers([True] * 6, failed_over=failed_over, gap=gap)
    assert check.judge(values, LIMITS)[0] == correct
