"""The model-FLOP counts the step_mfu metrics divide come from the plain
references' forward passes and match the published figures."""

import json
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  puts the checkout on sys.path
from benchmark.reference import resnet50, yolov3

CONFIGS = Path(resnet50.__file__).resolve().parents[1] / "configs"


def _cfg(name):
    with open(CONFIGS / f"{name}.json") as f:
        return json.load(f)


def test_resnet50_forward_is_the_papers_3_8_g_multiply_adds():
    # arXiv:1512.03385 table 1 gives 3.8e9 multiply-adds for the
    # 50-layer column, whose stride sits on the first 1x1 as it does
    # here (torchvision's v1.5 layout, stride on the 3x3, has 4.1e9)
    flops = resnet50.forward_flops_per_image(_cfg("resnet50"))
    assert flops == 7_715_946_496
    assert flops / 2 == pytest.approx(3.8e9, rel=0.02)
    assert resnet50.train_flops_per_image(_cfg("resnet50")) == 3 * flops


@pytest.mark.parametrize("size,published", [(608, 140.69e9), (416, 65.86e9)])
def test_yolov3_forward_matches_darknets_bflops(size, published):
    # darknet prints 65.86 BFLOPs for yolov3.cfg at 416 and 140.69 at 608
    cfg = dict(_cfg("yolov3"), input_size=size)
    assert yolov3.forward_flops_per_image(cfg) == pytest.approx(
        published, rel=1e-3)
