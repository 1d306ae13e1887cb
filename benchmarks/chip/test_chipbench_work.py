"""Operation and byte counts against a hand count, and the peaks table."""
import json
import os

import pytest

from chipbench import work

BENCH = os.path.dirname(os.path.abspath(__file__))


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def test_6c12c_hand_count():
    m, B = model("cnn_elm_6c12c"), 200
    # 28x28x1 -> 24x24x6 (pool 12x12x6) -> 8x8x12 (pool 4x4x12)
    conv1, conv2 = 2 * 24 * 24 * 25 * 1 * 6, 2 * 8 * 8 * 25 * 6 * 12
    assert work.feature_dim(m) == 192
    fwd = work.conv_forward(m, B)
    assert fwd["flops"] == B * (conv1 + conv2) == B * 403_200
    assert fwd["bytes"] == 4 * (B * (784 + 3456) + 150
                                + B * (864 + 768) + 1800)
    stats = work.stats(m, B)
    assert stats["flops"] == B * (2 * 192 ** 2 + 2 * 192 * 10)
    assert work.solve_flops(m) == 192 ** 3 / 3 + 2 * 192 ** 2 * 10
    step = work.step(m, B, sgd=True)
    # forward once, dW of both stages, dX of stage 2, stats, Hβ and its
    # transpose product, the solve: about 1.1 MFLOP an image
    useful = B * (403_200 + conv1 + 2 * conv2 + 77_568 + 4 * 192 * 10) \
        + 3_096_576
    assert step["useful_flops"] == useful
    assert 1.1e6 < useful / B < 1.2e6
    # the program runs the features twice: stats, then inside the grad
    assert step["conv_flops"] == B * (2 * 403_200 + conv1 + 2 * conv2)


def test_3c9c_hand_count():
    m = model("cnn_elm_3c9c")
    assert work.feature_dim(m) == 4 * 4 * 9 == 144
    conv = work.convs(m, 1)
    assert [c.flops for c in conv] == [2 * 24 * 24 * 25 * 3,
                                       2 * 8 * 8 * 25 * 3 * 9]
    step = work.step(m, 200, sgd=False)
    assert step["useful_flops"] == 200 * (86_400 + 86_400) + 200 * (
        2 * 144 ** 2 + 2 * 144 * 20)
    assert step["conv_flops"] == 200 * 172_800


def test_job_counts_every_member_batch_and_solve():
    m = model("cnn_elm_6c12c")
    j = work.job(m, members=4, batches=300, batch=200, epochs=1)
    assert j["images"] == 240_000
    assert j["useful_flops"] == 4 * 300 * work.step(m, 200, True)[
        "useful_flops"] + 4 * work.solve_flops(m)
    elm_only = work.job(m, members=4, batches=300, batch=200, epochs=0)
    assert elm_only["images"] == 240_000


def test_roofline_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.roofline_s(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.roofline_s(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_peaks_refuse_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peaks("TPU v9 imaginary")
