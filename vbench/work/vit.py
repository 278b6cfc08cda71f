"""Operations and bytes of the ViT / DeiT forward, from its shapes.

An operation is a multiply or an add of a matrix product (2 per
multiply-accumulate).  Layer norms, softmax, GELU and residual adds are
not counted, so a rate computed from these counts never overstates the
work.  Bytes are the least that must cross HBM for a call: its weights
once, its input activation read once and its output written once.
"""

from __future__ import annotations

from typing import Any, Mapping

F32 = 4


def _dims(g: Mapping[str, Any]):
    n = (int(g["image"]) // int(g["patch"])) ** 2
    return (n, int(g["dim"]), int(g["heads"]), int(g["layers"]),
            int(g["mlp_hidden"]), int(g["patch"]) ** 2 * 3,
            int(g["n_classes"]))


def layer_ops_per_image(g: Mapping[str, Any]) -> float:
    """One encoder layer on one image: q/k/v, scores, scores x v, the
    attention output projection, and the two MLP products."""
    n, d, h, _, m, _, _ = _dims(g)
    macs = 3 * n * d * d + 2 * n * n * d + n * d * d + 2 * n * d * m
    return 2.0 * macs


def embed_ops_per_image(g: Mapping[str, Any]) -> float:
    n, d, _, _, _, pdim, _ = _dims(g)
    return 2.0 * n * pdim * d


def head_ops_per_image(g: Mapping[str, Any]) -> float:
    _, d, _, _, _, _, n_cls = _dims(g)
    return 2.0 * d * n_cls


def model_ops_per_image(g: Mapping[str, Any]) -> float:
    """The whole forward of one image, classifier head included."""
    return (embed_ops_per_image(g) + int(g["layers"]) * layer_ops_per_image(g)
            + head_ops_per_image(g))


def layer_weight_bytes(g: Mapping[str, Any], weight_bytes: int) -> float:
    """One encoder layer's weights at ``weight_bytes`` per value, with the
    float32 per-channel scales of a quantized layer (``weight_bytes`` 1)
    and the float32 norm and bias vectors."""
    _, d, _, _, m, _, _ = _dims(g)
    values = 4 * d * d + 2 * d * m
    vectors = 4 * d + m + d
    scales = (3 * d + d + m + d) if weight_bytes < F32 else 0
    return float(values * weight_bytes + (vectors + scales) * F32)


def layer_call(g: Mapping[str, Any], batch: int, weight_bytes: int):
    """(operations, least bytes) of one call of the encoder-layer kernel
    on ``batch`` images (padding rows included: the kernel computes
    them).  The activation enters and leaves in float32."""
    n, d, _, _, _, _, _ = _dims(g)
    ops = batch * layer_ops_per_image(g)
    act = 2.0 * batch * n * d * F32
    return ops, layer_weight_bytes(g, weight_bytes) + act


def layer_calls(g: Mapping[str, Any], batch: int, weight_bytes: int):
    """(operations, least bytes) of each encoder-layer kernel call of one
    forward on ``batch`` images: ``layers`` identical calls."""
    return [layer_call(g, batch, weight_bytes)] * int(g["layers"])
