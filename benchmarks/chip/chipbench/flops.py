"""Model FLOPs from shapes: the benchmark's yardstick for ``mfu``.

A copy of the program's ResNet-18 layer count (2 FLOPs per multiply-add,
per image, forward only).  Training counts 3x the forward pass (forward,
backward through the inputs, backward through the weights); recomputed
work does not count.
"""
from __future__ import annotations

from typing import List, Tuple

TRAIN_MULT = 3.0


def conv2d_flops(h_out: float, w_out: float, c_in: float, c_out: float,
                 kh: int, kw: int) -> float:
    """Forward FLOPs of one conv2d over one image."""
    return 2.0 * h_out * w_out * c_out * c_in * kh * kw


def resnet18_layers(img: int, n_classes: int,
                    act_bits: int = 32) -> List[Tuple[str, float, float,
                                                      float]]:
    """``(name, fwd_flops, param_bytes, out_bits)`` per cuttable unit:
    stem, four stages of two basic blocks, head (He et al. 2016, Table 1).
    """
    layers = []

    def block(name, res, c_in, c_out, downsample):
        f = conv2d_flops(res, res, c_in, c_out, 3, 3)
        f += conv2d_flops(res, res, c_out, c_out, 3, 3)
        p = (c_in * c_out + c_out * c_out) * 9 * 4.0 + 4 * c_out * 4.0
        if downsample:
            f += conv2d_flops(res, res, c_in, c_out, 1, 1)
            p += c_in * c_out * 4.0
        layers.append((name, f, p, res * res * c_out * act_bits))

    r = img // 2                          # 7x7/2 stem conv, then maxpool/2
    layers.append(("stem", conv2d_flops(r, r, 3, 64, 7, 7),
                   (3 * 64 * 49 + 2 * 64) * 4.0,
                   (img // 4) ** 2 * 64 * act_bits))
    r = img // 4
    block("s1b1", r, 64, 64, False)
    block("s1b2", r, 64, 64, False)
    r //= 2
    block("s2b1", r, 64, 128, True)
    block("s2b2", r, 128, 128, False)
    r //= 2
    block("s3b1", r, 128, 256, True)
    block("s3b2", r, 256, 256, False)
    r //= 2
    block("s4b1", r, 256, 512, True)
    block("s4b2", r, 512, 512, False)
    layers.append(("head", 2.0 * 512 * n_classes, 512 * n_classes * 4.0,
                   n_classes * act_bits))
    return layers


def resnet18_fwd_flops(img: int, n_classes: int) -> float:
    """Forward FLOPs of the whole network for one image."""
    return sum(f for _, f, _, _ in resnet18_layers(img, n_classes))


def resnet18_train_flops(img: int, n_classes: int) -> float:
    """Training FLOPs (forward + backward) for one image."""
    return TRAIN_MULT * resnet18_fwd_flops(img, n_classes)


def resnet18_cut_costs(img: int, n_classes: int):
    """Per cut ``l`` in 1..9: ``(w1, w2, dtx_bits, d_isl_bits)`` per item,
    with W1/W2 the training FLOPs of the two segments, D_tx the boundary
    activation bits and D_ISL the segment-A weights in bits."""
    layers = resnet18_layers(img, n_classes)
    out = []
    for cut in range(1, len(layers)):
        a, b = layers[:cut], layers[cut:]
        out.append((TRAIN_MULT * sum(x[1] for x in a),
                    TRAIN_MULT * sum(x[1] for x in b),
                    layers[cut - 1][3],
                    8.0 * sum(x[2] for x in a)))
    return out
