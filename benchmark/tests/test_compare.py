"""The comparison: norms by the worst leaf, each number against its own
limit; bf16's error passes and int8's fails at limits set between them."""

import numpy as np
import pytest

from benchmark import compare


def test_worst_leaf_gap_is_scaled_by_the_median_leaf():
    reference = np.array([1.0, 2.0, 4.0, 1e-9])
    program = np.array([1.0, 2.2, 4.0, 2e-9])
    # leaf 1 is off by 0.2 of 2.0; the all-but-zero leaf is held to the
    # median leaf's norm (1.5), not to its own.
    assert compare.worst_leaf_norm_gap(program, reference) == pytest.approx(0.1)


def test_leaf_counts_must_match():
    with pytest.raises(ValueError):
        compare.worst_leaf_norm_gap([1.0], [1.0, 2.0])


LIMITS = {"first_grad_rel_diff": 0.04, "loss_gap.step1": 0.001}


@pytest.mark.parametrize("numbers, expected", [
    ({"first_grad_rel_diff": 0.012, "loss_gap.step1": 1e-4}, True),   # bf16's error
    ({"first_grad_rel_diff": 0.105, "loss_gap.step1": 1e-4}, False),  # int8's error
    ({"first_grad_rel_diff": float("nan"), "loss_gap.step1": 1e-4}, False),
])
def test_decide(numbers, expected):
    correct, rows = compare.decide(numbers, LIMITS)
    assert correct is expected
    assert [r["check"] for r in rows] == list(numbers)
    assert all(r["limit"] == LIMITS[r["check"]] for r in rows)


def test_a_number_without_a_limit_is_an_error():
    with pytest.raises(KeyError):
        compare.decide({"unheld": 0.0}, LIMITS)
