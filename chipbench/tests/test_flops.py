import pytest

import flops


def test_conv2d_by_hand():
    # 7x7/2 stem on 224x224x3 -> 112x112x64: 2*49*3*64*112*112
    f, h, w = flops.conv2d(224, 224, 3, 64, 7, 2, 3)
    assert (h, w) == (112, 112) and f == 2 * 49 * 3 * 64 * 112 * 112


def test_resnet50_forward_matches_the_paper():
    # He et al. 2015, table 1: 3.8e9 multiply-adds for the 50-layer net
    # (stride on the bottleneck's first 1x1, as MXNet's model zoo has
    # it; torchvision's "v1.5" with the stride on the 3x3 is 4.1e9)
    f = flops.resnet_v1_forward(50, 224, 1000)
    assert f / 2 == pytest.approx(3.86e9, rel=0.01)
    # one stage by hand: stage 4's last block at 7x7
    block = 2 * 49 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048)
    assert block == 2 * 49 * 4456448


def test_resnet18_forward():
    # torchvision counts 1.81e9 multiply-adds for ResNet-18 at 224
    assert flops.resnet_v1_forward(18, 224, 1000) / 2 == pytest.approx(
        1.81e9, rel=0.01)


def test_bert_base_s384_by_hand():
    s, d, ff = 384, 768, 3072
    layer = 2 * s * (4 * d * d) + 4 * s * s * d + 2 * s * (2 * d * ff)
    assert flops.bert_forward(12, d, ff, s) == 12 * layer + 2 * s * d * 2
    assert flops.bert_forward(12, d, ff, s) == pytest.approx(70.7e9, rel=0.01)


def test_train_is_three_forwards_and_unknown_builder_raises():
    cfg = {"builder": "resnet_v1", "num_layers": 50, "image": 224,
           "classes": 1000}
    assert flops.train_flops(cfg) == 3 * flops.forward_flops(cfg)
    with pytest.raises(ImportError):
        flops.forward_flops({"builder": "nope"})


def test_peaks_unknown_device_raises():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")

