"""The join's byte count and the table of peaks."""

from __future__ import annotations

import pytest

from benchmark import gen, work


def test_join_bytes_are_those_of_the_inputs_and_the_result():
    # 8 replicas x 156,250 objects at the north-star width: 6.94 GB
    bpo = gen.bytes_per_object({"num_actors": 64, "member_capacity": 16,
                                "deferred_capacity": 2, "counter_bytes": 4})
    assert bpo == 4936
    assert work.join_bytes(8, 156_250, bpo) == 9 * 156_250 * 4936


@pytest.mark.parametrize("replicas", [2, 3, 8])
def test_a_tree_and_a_fused_fold_need_the_same_bytes(replicas):
    # the count depends on the inputs and the result only: a pairwise
    # tree (which writes intermediates) and a fused fold are held to it
    n, bpo = 1000, 4936
    fused = work.join_bytes(replicas, n, bpo)
    tree_inputs_and_result = (replicas + 1) * n * bpo
    assert fused == tree_inputs_and_result


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    with pytest.raises(KeyError):
        work.roofline_pct(1e9, 1.0, "cpu")


def test_roofline_share():
    peak = work.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    assert work.roofline_pct(peak, 1.0, "TPU v5 lite") == pytest.approx(100)
    assert work.roofline_pct(4 * peak, 2.0, "TPU v5 lite", chips=4) == \
        pytest.approx(50)
    assert work.roofline_pct(1.0, 0.0, "TPU v5 lite") is None
