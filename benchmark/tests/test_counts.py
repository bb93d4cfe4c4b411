"""The benchmark's own work counts: a hand-worked example, and that they
read the data's degrees and the rank alone."""

import numpy as np

from benchmark.lib import counts, peaks


def test_three_user_example():
    # users rate 1, 2 and 5 items; items are rated 3, 2, 2, 1 times; R = 4
    du, di, R = np.array([1, 2, 5]), np.array([3, 2, 2, 1]), 4
    nnz = 8
    # per side: Gram 2*nnz*R^2 + rhs 2*nnz*R + sum min(n, R)^3 / 3
    user = 2 * nnz * 16 + 2 * nnz * 4 + (1 + 8 + 64) / 3
    item = 2 * nnz * 16 + 2 * nnz * 4 + (27 + 8 + 8 + 1) / 3
    assert counts.als_side_flops(du, R) == user
    assert counts.als_iteration_flops(du, di, R) == user + item
    # per side: a counterpart row per rating, index + value per rating, and
    # each solved row written once, at 4 bytes a factor
    assert counts.als_side_bytes(du, R) == nnz * 16 + nnz * 8 + 3 * 16
    assert counts.als_iteration_bytes(du, di, R) == (
        nnz * 16 + nnz * 8 + 3 * 16) + (nnz * 16 + nnz * 8 + 4 * 16)
    # an entity with no rating is not solved
    assert counts.als_side_flops(np.array([0, 1, 2, 5]), R) == user


def test_counts_do_not_move_with_the_programs_plan():
    from predictionio_tpu.ops.ratings import RatingsCOO, plan_for_users
    rng = np.random.default_rng(0)
    u = np.sort(rng.integers(0, 300, 6000)).astype(np.int32)
    i = rng.integers(0, 200, 6000).astype(np.int32)
    coo = RatingsCOO(u, i, np.ones(6000, np.float32), 300, 200)
    a = plan_for_users(coo, work_budget=1 << 10, bucket_ratio=1.125)
    b = plan_for_users(coo, work_budget=1 << 12, bucket_ratio=2.0)
    assert a.kernel_shapes != b.kernel_shapes      # the plan did move
    deg = np.bincount(u, minlength=300)
    assert counts.als_side_flops(deg, 200) == counts.als_side_flops(
        np.bincount(u, minlength=300), 200)
    # the count takes degrees and a rank: there is no plan to pass it
    import inspect
    assert list(inspect.signature(counts.als_side_flops).parameters) == [
        "degrees", "rank"]


def test_topk_and_roofline():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert counts.topk_query_flops(1000, 200) == 2 * 1000 * 200
    assert counts.topk_dispatch_bytes(1000, 200, 16) == (1000 + 16) * 800
    t, bound = counts.roofline_seconds(197e12, 819e9 * 2, p)
    assert (t, bound) == (2.0, "hbm")
    try:
        peaks.peaks_for("TPU v9")
    except KeyError as e:
        assert "no published peaks" in str(e)
    else:
        raise AssertionError("an unknown device kind must be an error")
