"""Plain reference copy of the paper's workload layer tables.

Each workload is a list of GEMM layers (M, K, N): M input vectors per
inference (a convolution's H_out * W_out), K the reduction depth
(C_in * k_h * k_w) and N the outputs. Depthwise convolutions are
(H*W, k*k, C). Written out from the networks' published layer
structure for the benchmark's reference, independently of the program.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

Layer = Tuple[float, float, float]


def _conv(hw: int, cin: int, k: int, cout: int) -> Layer:
    return (float(hw * hw), float(cin * k * k), float(cout))


def _dw(hw: int, c: int, k: int) -> Layer:
    return (float(hw * hw), float(k * k), float(c))


def _fc(cin: int, cout: int) -> Layer:
    return (1.0, float(cin), float(cout))


def resnet18() -> List[Layer]:
    layers = [_conv(112, 3, 7, 64)]
    for cin, cout, hw, nblk in [(64, 64, 56, 2), (64, 128, 28, 2),
                                (128, 256, 14, 2), (256, 512, 7, 2)]:
        for b in range(nblk):
            layers.append(_conv(hw, cin if b == 0 else cout, 3, cout))
            layers.append(_conv(hw, cout, 3, cout))
        if cin != cout:
            layers.append(_conv(hw, cin, 1, cout))
    layers.append(_fc(512, 1000))
    return layers


def resnet50() -> List[Layer]:
    layers = [_conv(112, 3, 7, 64)]
    for cin, cout, hw, nblk in [(64, 256, 56, 3), (256, 512, 28, 4),
                                (512, 1024, 14, 6), (1024, 2048, 7, 3)]:
        mid = cout // 4
        for b in range(nblk):
            layers.append(_conv(hw, cin if b == 0 else cout, 1, mid))
            layers.append(_conv(hw, mid, 3, mid))
            layers.append(_conv(hw, mid, 1, cout))
        layers.append(_conv(hw, cin, 1, cout))
    layers.append(_fc(2048, 1000))
    return layers


def vgg16() -> List[Layer]:
    convs = [(224, 3, 64), (224, 64, 64), (112, 64, 128), (112, 128, 128),
             (56, 128, 256), (56, 256, 256), (56, 256, 256),
             (28, 256, 512), (28, 512, 512), (28, 512, 512),
             (14, 512, 512), (14, 512, 512), (14, 512, 512)]
    return ([_conv(hw, ci, 3, co) for hw, ci, co in convs]
            + [_fc(25088, 4096), _fc(4096, 4096), _fc(4096, 1000)])


def alexnet() -> List[Layer]:
    return [(55.0 * 55, 3.0 * 121, 64.0), (27.0 * 27, 64.0 * 25, 192.0),
            (13.0 * 13, 192.0 * 9, 384.0), (13.0 * 13, 384.0 * 9, 256.0),
            (13.0 * 13, 256.0 * 9, 256.0),
            _fc(9216, 4096), _fc(4096, 4096), _fc(4096, 1000)]


def mobilenetv3() -> List[Layer]:
    """MobileNetV3-Large inverted-residual blocks (hw, cin, exp, cout, k)."""
    layers = [_conv(112, 3, 3, 16)]
    blocks = [
        (112, 16, 16, 16, 3), (56, 16, 64, 24, 3), (56, 24, 72, 24, 3),
        (28, 24, 72, 40, 5), (28, 40, 120, 40, 5), (28, 40, 120, 40, 5),
        (14, 40, 240, 80, 3), (14, 80, 200, 80, 3), (14, 80, 184, 80, 3),
        (14, 80, 184, 80, 3), (14, 80, 480, 112, 3), (14, 112, 672, 112, 3),
        (7, 112, 672, 160, 5), (7, 160, 960, 160, 5), (7, 160, 960, 160, 5),
    ]
    for hw, cin, exp, cout, k in blocks:
        if exp != cin:
            layers.append(_conv(hw, cin, 1, exp))
        layers.append(_dw(hw, exp, k))
        layers.append(_conv(hw, exp, 1, cout))
    return layers + [_conv(7, 160, 1, 960), _fc(960, 1280), _fc(1280, 1000)]


def densenet201() -> List[Layer]:
    layers = [_conv(112, 3, 7, 64)]
    growth, c = 32, 64
    for hw, n in [(56, 6), (28, 12), (14, 48), (7, 32)]:
        for _ in range(n):
            layers.append(_conv(hw, c, 1, 4 * growth))
            layers.append(_conv(hw, 4 * growth, 3, growth))
            c += growth
        if hw != 7:
            layers.append(_conv(hw // 2, c, 1, c // 2))
            c //= 2
    layers.append(_fc(c, 1000))
    return layers


def _transformer(seq: int, d: int, ff: int, n_layers: int,
                 vocab: int) -> List[Layer]:
    s = float(seq)
    per_block = [(s, d, 3.0 * d), (s, d, d), (s, d, ff), (s, ff, d)]
    return [tuple(map(float, l)) for _ in range(n_layers)
            for l in per_block] + [(s, float(d), float(vocab))]


def vit_b16() -> List[Layer]:
    return [(196.0, 768.0, 768.0)] + _transformer(197, 768, 3072, 12, 1000)


def mobilebert() -> List[Layer]:
    seq, d, intra = 128.0, 512.0, 128.0
    layers: List[Layer] = []
    for _ in range(24):
        layers += [(seq, d, intra), (seq, intra, 3 * intra),
                   (seq, intra, intra)]
        layers += [(seq, intra, 4 * intra), (seq, 4 * intra, intra)] * 4
        layers.append((seq, intra, d))
    return layers + [(seq, d, 30522.0)]


def gpt2_medium() -> List[Layer]:
    return _transformer(1024, 1024, 4096, 24, 50257)


WORKLOADS = {f.__name__: f for f in (
    resnet18, resnet50, vgg16, alexnet, mobilenetv3, densenet201,
    vit_b16, mobilebert, gpt2_medium)}

# Clean 8-bit accuracies of the paper's accuracy study (§IV-H); other
# workloads take 0.90.
BASE_ACCURACY = {"resnet18": 0.9488, "vgg16": 0.9789, "alexnet": 0.9350,
                 "mobilenetv3": 0.7003}


def layer_table(name: str) -> Dict[str, np.ndarray]:
    """{'layers': (L, 3) float64, 'stored': weights held on chip}."""
    layers = np.asarray(WORKLOADS[name](), np.float64)
    return {"layers": layers,
            "stored": float(np.sum(layers[:, 1] * layers[:, 2]))}
