"""FLOP and byte functions against values worked out by hand."""

import json
import os

import pytest

from benchmark import flops, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_nature_encoder_by_hand():
    # 84x84x1 -> 20x20x32 (8x8/4) -> 9x9x64 (4x4/2) -> 7x7x64 (3x3/1) -> 512
    conv1 = 20 * 20 * 32 * (8 * 8 * 1) * 2
    conv2 = 9 * 9 * 64 * (4 * 4 * 32) * 2
    conv3 = 7 * 7 * 64 * (3 * 3 * 64) * 2
    dense = 3136 * 512 * 2
    assert (conv1, conv2, conv3, dense) == (1638400, 5308416, 3612672, 3211264)
    assert flops.nature_encoder_flops_per_frame((84, 84, 1), 512) == 13770752


def test_cores_and_heads_by_hand():
    d = 512 + 3 + 1
    assert flops.core_flops_per_step("lstm", 512, 3) == 2 * (d + 512) * 2048 == 4210688
    assert flops.core_flops_per_step("lru", 512, 3) == 2 * (3 * d * 512 + 2 * 512 * 512) == 2633728
    assert flops.core_flops_per_step("lru", 512, 3, lru_chunk=128) == 2633728 + 2 * 4 * 128 * 512
    assert flops.heads_flops_per_step(512, 3) == 2 * (2 * 512 * 512 + 512 * 3 + 512) == 1052672
    with pytest.raises(KeyError):
        flops.core_flops_per_step("gru", 512, 3)


@pytest.mark.parametrize("name,expect", [
    # trunk x (T + 2L + T) + heads x 5L, times B
    ("nature-lstm512", 64 * ((13770752 + 4210688) * (85 + 80 + 85) + 1052672 * 200)),
    ("lru-seq581", 32 * ((13770752 + 2633728) * (581 + 1024 + 581) + 1052672 * 2560)),
    ("nature-lstm512-dp4", 64 * ((13770752 + 4210688) * 250 + 1052672 * 200)),
])
def test_update_flops_of_the_three_configs(name, expect):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as fh:
        cfg = harness.build_config(json.load(fh), 0)
    # through the reference module the configuration names, as readers/mfu.py asks
    cell = harness.load_cell(ROOT, name + ".learn")
    assert harness.reference_for(cell).update_flops(cfg) == expect


def test_lstm_kernel_costs_and_roofline_by_hand():
    T, B, H = 85, 64, 512
    f, b = flops.lstm_fwd_kernel_cost(T, B, H, 2)
    assert f == 85 * 2 * 64 * 512 * 2048 == 11408506880
    assert b == 85 * 64 * 2048 * 2 + 512 * 2048 * 2 + 2 * 64 * 512 * 4 + 85 * 64 * 512 * 2 + 85 * 64 * 512 * 4
    fb, bb = flops.lstm_seq_bwd_kernel_cost(T, B, H, 2)
    assert fb == 2 * f
    assert bb == (85 * 64 * 512 * 4 + 85 * 64 * 2048 * 2 + 85 * 64 * 512 * 2 + 2 * 85 * 64 * 512 * 4
                  + 512 * 2048 * 2 + 64 * 512 * 4 + 85 * 64 * 2048 * 4)
    peaks = flops.device_peaks("TPU v5 lite")
    t, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "compute" and t == pytest.approx(f / 197e12)
    t_mem, bound_mem = flops.roofline_seconds(1, 819e9, peaks)
    assert bound_mem == "memory" and t_mem == pytest.approx(1.0)
    total, _ = flops.lstm_kernels_roofline_seconds_per_update(T, B, H, 2, peaks)
    assert total == pytest.approx(2 * t + flops.roofline_seconds(fb, bb, peaks)[0])


def test_unknown_device_is_an_error_not_a_default():
    assert flops.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            flops.device_peaks(kind)
