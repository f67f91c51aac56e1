"""Operations per sample, counted from a configuration's shapes.

A multiply-add is two operations. Convolutions are 3x3 with "same"
padding and stride 1, each optionally followed by a 2x2 max pool; fully
connected layers are plain matrix products. Bias, ReLU and pooling are
not counted. Training counts three forward passes: the forward itself
and, in the backward pass, one product for the input gradient and one
for the weight gradient.
"""
from __future__ import annotations

from typing import Sequence


def forward_per_sample(layers: Sequence[Sequence], image: Sequence[int]
                       ) -> int:
    h, w, _ = image
    total = 0
    for layer in layers:
        if layer[0] == "conv":
            _, cin, cout, pool = layer
            total += 2 * h * w * 9 * cin * cout
            if pool:
                h, w = h // 2, w // 2
        else:
            _, fin, fout = layer
            total += 2 * fin * fout
    return total


def train_per_sample(layers: Sequence[Sequence], image: Sequence[int]
                     ) -> int:
    return 3 * forward_per_sample(layers, image)
