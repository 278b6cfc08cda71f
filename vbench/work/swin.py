"""Operations and bytes of the Swin forward, from its shapes.

Counted as in ``vbench.work.vit``: an operation is a multiply or an add
of a matrix product (2 per multiply-accumulate); layer norms, softmax,
GELU, residual adds and the window fold are not counted.  Bytes are the
least that must cross HBM for a call: its weights once, its bias and
mask operands once, its input activation read once and its output
written once.
"""

from __future__ import annotations

from typing import Any, List, Mapping, Tuple

F32 = 4


def stages(g: Mapping[str, Any]) -> List[Tuple[int, int, int, int, int]]:
    """(depth, width D, heads, windows, side) of each stage: the map's
    side halves and the width doubles at each merge."""
    side = int(g["image"]) // int(g["patch"])
    win = int(g["window"])
    d = int(g["embed_dim"])
    out = []
    for depth, h in zip(g["depths"], g["heads"]):
        out.append((int(depth), d, int(h), (side // win) ** 2, side))
        side //= 2
        d *= 2
    return out


def window_layer_ops(g: Mapping[str, Any], d: int, n_windows: int) -> float:
    """One windowed layer of width ``d`` on one image: q/k/v, scores,
    scores x v, the output projection and the two MLP products, over
    ``n_windows`` windows."""
    n = int(g["window"]) ** 2
    m = int(d * float(g["mlp_ratio"]))
    macs = 4 * n * d * d + 2 * n * n * d + 2 * n * d * m
    return 2.0 * n_windows * macs


def model_ops_per_image(g: Mapping[str, Any]) -> float:
    """The whole forward of one image: patch embedding, every layer, the
    merges (4D -> 2D on a quarter of the tokens) and the head."""
    side = int(g["image"]) // int(g["patch"])
    c = int(g["embed_dim"])
    ops = 2.0 * side * side * int(g["patch"]) ** 2 * 3 * c
    st = stages(g)
    for i, (depth, d, _, n_w, s) in enumerate(st):
        ops += depth * window_layer_ops(g, d, n_w)
        if i < len(st) - 1:
            ops += 2.0 * (s // 2) ** 2 * 4 * d * 2 * d
    return ops + 2.0 * st[-1][1] * int(g["n_classes"])


def layer_calls(g: Mapping[str, Any], batch: int, weight_bytes: int):
    """(operations, least bytes) of each windowed encoder-layer kernel
    call of one forward on ``batch`` images, in order: each stage's
    ``depth`` calls over batch x windows of window^2 tokens at its
    width, with its weights at ``weight_bytes`` per value (and the
    float32 per-channel scales where quantized), the (H, n, n) bias and
    (nW, n, n) mask in float32, and the activation in and out in
    float32."""
    n = int(g["window"]) ** 2
    calls = []
    for depth, d, h, n_w, _ in stages(g):
        m = int(d * float(g["mlp_ratio"]))
        weights = (4 * d * d + 2 * d * m) * weight_bytes
        vectors = 4 * d + m + d
        scales = (3 * d + d + m + d) if weight_bytes < F32 else 0
        operands = (h + n_w) * n * n
        act = 2.0 * batch * n_w * n * d
        nbytes = weights + (vectors + scales + operands + act) * F32
        calls += [(batch * window_layer_ops(g, d, n_w), float(nbytes))] \
            * depth
    return calls
