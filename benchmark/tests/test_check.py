"""The comparison's arithmetic, on hand numbers."""
import numpy as np
import pytest

from benchmark import check, harness
from benchmark.reducers import percentile


def test_norm_gaps_are_gaps_of_norms_against_leaf_or_median():
    want = {"a": 1.0, "b": 0.01, "c": 0.02}        # median 0.02
    got = {"a": 1.05, "b": 0.012, "c": 0.02}
    gaps = check.norm_gaps(got, want, want)
    # a: 0.05 / 1.0; b: 0.002 / max(0.01, 0.02) = 0.1 -> b is the worst
    assert check.worst(gaps) == (pytest.approx(0.1), "b")
    assert check.median(gaps) == pytest.approx(0.05)
    bad = check.norm_gaps({**got, "a": float("nan")}, want, want)
    assert check.worst(bad)[0] != check.worst(bad)[0]   # NaN is never "ok"
    assert check.median(bad) != check.median(bad)


def test_rel_diffs_see_direction_not_only_size():
    want = {"op": {"w": np.array([3.0, 4.0]), "v": np.array([1.0, 0.0])}}
    got = {"op": {"w": np.array([4.0, 3.0]), "v": np.array([1.0, 0.0])}}
    d = check.rel_diffs(got, want)
    assert d["op/v"] == 0.0
    assert d["op/w"] == pytest.approx(np.sqrt(2.0) / 5.0)    # same norm, moved


def test_moving_leaves_rule_is_on_the_reference_gradient():
    g = {"w1": 1.0, "w2": 2.0, "w3": 3.0, "bk": 1e-9}
    assert check.moving_leaves(g) == ["w1", "w2", "w3"]


def test_train_checks_and_limits():
    ref = {"loss": 0.7, "moment_norms": {"w": 1.0, "bk": 1e-9, "v": 2.0},
           "delta_norms": {"w": 1.0, "bk": 5.0, "v": 1.0},
           "grad1_norms": {"w": 1.0, "bk": 1e-9, "v": 2.0}}
    prog = {"loss": 0.7007, "moment_norms": {"w": 1.02, "bk": 1e-9, "v": 2.0},
            "delta_norms": {"w": 1.0, "bk": 50.0, "v": 1.1},
            "moment_rel_diffs": {"w": 0.03, "bk": 0.9, "v": 0.01}}
    limits = {"moment_rel_diff_worst": 0.5, "moment_rel_diff_median": 0.05,
              "update_norm_gap": 0.05, "loss_gap": 0.01}
    checks, notes = check.train_checks(prog, ref, limits)
    assert checks["loss_gap"]["value"] == pytest.approx(0.001)
    assert notes["moment_norm_gap_worst"] == pytest.approx(0.02)
    assert notes["all_moment_norm_gap_median"] == pytest.approx(0.0)
    assert checks["moment_rel_diff_worst"]["value"] == pytest.approx(0.9)
    assert checks["moment_rel_diff_median"]["value"] == pytest.approx(0.03)
    # bk is left out of the update's comparison by the gradient rule
    assert checks["update_norm_gap"]["value"] == pytest.approx(0.1)
    assert notes["leaves_compared"] == 2 and notes["update_leaf"] == "v"
    assert not harness.Record({}, 1, 0, checks, 0).correct
    unchanged = {**prog, "delta_norms": {"w": 0.0, "bk": 0.0, "v": 0.0}}
    c2, _ = check.train_checks(unchanged, ref, limits)
    assert c2["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_serve_checks_widest_gap():
    checks, notes = check.serve_checks(
        [np.array([0.0, 0.0, 0.3]), np.array([0.0])],
        {"served_logit_gap_max": 0.1, "served_off_best_share": 0.3,
         "unanswered": 0})
    assert checks["served_logit_gap_max"]["value"] == pytest.approx(0.3)
    assert checks["served_off_best_share"]["value"] == pytest.approx(0.25)
    assert notes["tokens_compared"] == 4 and notes["tokens_off_best"] == 1
    assert not harness.Record({}, 1, 0, checks, 0).correct


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert percentile(vals, 90) == 90 and percentile(vals, 95) == 95
    assert percentile([5.0], 90) == 5.0
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
