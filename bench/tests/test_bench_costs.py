"""The operation and byte counts against hand counts."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import files  # noqa: E402


def test_layer_agg_bytes_at_the_paper_width_stack():
    flops, nbytes = files.kernel_cost("layer_agg").cost(N=16, R=11088, D=1024)
    # updates 16*11088*1024, mask 16*11088, result 11088*1024, f32
    assert nbytes == 4 * (181_665_792 + 177_408 + 11_354_112)
    assert flops == 2 * 181_665_792 + 2 * 177_408 + 11_354_112


def test_resnet18_forward_flops_by_hand():
    _, ref = files.config("resnet18-cifar10")
    stem = 2 * 9 * 3 * 64 * 32 * 32                       # 3,538,944
    conv = {64: 2 * 9 * 64 * 64 * 32 * 32}                # 75,497,472
    # a stage after the first: strided 3x3 + three 3x3 + 1x1 projection
    later = 37_748_736 + 3 * 75_497_472 + 4_194_304       # 268,435,456
    exit0 = 2 * 64 * 32 * 32 * 32 + 2 * 32 * 10
    assert ref.forward_flops(0, 32, 1.0) == stem + 4 * conv[64] + exit0
    full = (stem + 4 * conv[64] + 3 * later
            + sum(2 * c * (c // 2) * s * s + 2 * (c // 2) * 10
                  for c, s in ((64, 32), (128, 16), (256, 8), (512, 4))))
    assert full == 1_127_622_016
    assert ref.forward_flops(3, 32, 1.0) == full


def test_energy_model_flops_match_the_papers_cost_model():
    _, ref = files.config("resnet18-cifar10")
    from repro.models import cnn  # the program's Eq. 5/7 input
    for m in range(4):
        assert ref.energy_flops(m) == pytest.approx(cnn.flops_per_sample(m))
