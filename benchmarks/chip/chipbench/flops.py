"""Operations and bytes of the timed work, counted from shapes.

A model's count is that of its matrix multiplications, the work the
forward and backward passes require: the forward product of each layer,
its weight gradient, and its input gradient for every layer but the
first (the data needs no gradient). Elementwise work, pooling and the
loss are left out: they are a few per cent of the count and run on the
vector unit, not against the matrix unit's peak."""
from __future__ import annotations

import math


def cnn_layers(cfg: dict):
    """(rows, contraction, columns) of each matrix product in one
    sample's forward pass of the paper CNN: every 3x3 SAME convolution
    as its im2col product, each followed by a 2x2 max-pool, then the
    dense layers."""
    h, w, c = cfg["input_shape"]
    out = []
    for cout in cfg["conv_channels"]:
        out.append((h * w, 9 * c, cout))
        h, w, c = h // 2, w // 2, cout
    d = h * w * c
    for width in list(cfg["hidden"]) + [cfg["num_classes"]]:
        out.append((1, d, width))
        d = width
    return out


def mclr_layers(cfg: dict):
    """The one product of multinomial logistic regression."""
    d = math.prod(cfg["input_shape"])
    return [(1, d, cfg["num_classes"])]


def layers(cfg: dict):
    """The matrix products of ``cfg``'s model, by its ``kind``."""
    return {"cnn": cnn_layers, "mclr": mclr_layers}[cfg["kind"]](cfg)


def train_flops_per_sample(cfg: dict) -> int:
    """Forward plus backward FLOPs of one sample's gradient."""
    total = 0
    for i, (m, k, n) in enumerate(layers(cfg)):
        mm = 2 * m * k * n
        total += mm + mm + (mm if i > 0 else 0)
    return total


def param_sizes(cfg: dict):
    """Element counts of the model's parameter leaves, in the order the
    program's parameter tree flattens (sorted dict keys)."""
    sizes = {}
    if cfg["kind"] == "mclr":
        d = math.prod(cfg["input_shape"])
        sizes = {"b": cfg["num_classes"], "w": d * cfg["num_classes"]}
    else:
        h, w, c = cfg["input_shape"]
        for i, cout in enumerate(cfg["conv_channels"]):
            sizes[f"conv{i}/w"] = 9 * c * cout
            sizes[f"conv{i}/b"] = cout
            h, w, c = h // 2, w // 2, cout
        d = h * w * c
        for j, width in enumerate(list(cfg["hidden"]) + [cfg["num_classes"]]):
            sizes[f"dense{j}/w"] = d * width
            sizes[f"dense{j}/b"] = width
            d = width
    return [sizes[k] for k in sorted(sizes)]


LANES = 128
# per element of the padded (rows, 128) block: theta, gradient, anchor and
# momentum read, theta and momentum written, 4 bytes each
PROX_BYTES_PER_ELEM = 6 * 4
# g + lam * (theta - anchor), then theta - alpha * update
PROX_FLOPS_PER_ELEM = 5


def prox_padded_elems(leaf_elems: int) -> int:
    """Elements the prox kernel streams for one flattened leaf: the
    wrapper pads it to whole 128-lane rows."""
    return -(-leaf_elems // LANES) * LANES


def prox_step_cost(cfg: dict, stacked: int):
    """(flops, bytes) of one device step's prox kernels: one kernel per
    leaf over the ``stacked`` (M x N) device models."""
    elems = sum(prox_padded_elems(stacked * s) for s in param_sizes(cfg))
    return elems * PROX_FLOPS_PER_ELEM, elems * PROX_BYTES_PER_ELEM


def prox_step_roofline_s(cfg: dict, stacked: int, peak: dict) -> float:
    """The least time one device step's prox kernels could take on one
    chip: per kernel (one per leaf), the larger of its operations over
    the peak FLOP/s and its bytes over the peak bandwidth."""
    total = 0.0
    for s in param_sizes(cfg):
        elems = prox_padded_elems(stacked * s)
        total += roofline_seconds(elems * PROX_FLOPS_PER_ELEM,
                                  elems * PROX_BYTES_PER_ELEM, peak)
    return total


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over the peak FLOP/s and the bytes over the peak bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
