"""Plain reference of the Swin Transformer classifier, and its weights.

Written from the published description (Liu et al. 2021, "Swin
Transformer: Hierarchical Vision Transformer using Shifted Windows",
§3.1-3.3 and Table 1) in straightforward ``jax.numpy``: a patch
embedding with its layer norm, stages of blocks whose attention runs
inside non-overlapping windows, every second block of a stage on windows
shifted by half a window (cyclically, with the paper's region mask) where
the stage has more than one window, a learned relative-position bias per
head, patch merging (2x2 neighbours concatenated, layer norm, linear 4C ->
2C) between stages, and the classifier on mean-pooled tokens.  It carries
the departures each configuration file lists under ``departures``: no
bias on the patch embedding, q/k/v, the attention output or the head;
GELU in its tanh form; a merge that concatenates its 2x2 neighbours in
the order (0,0), (0,1), (1,0), (1,1) of (row, column) offsets; patches
flattened as (row, column, channel); and a mask value of -1e30.  It
imports nothing of the system under test.

``init_params`` makes the weights from a seed, on the device, in one
jitted call, in the pytree layout the served program reads:
``stages[s]["blocks"][b]`` with per-head ``wq/wk/wv`` of shape
(H, D, Dh), the attention output projection ``w_msa`` (H*Dh, D) with
head-major rows, the bias table ``rel_bias`` ((2w-1)^2, H) and the MLP,
and ``merge_ln_w/merge_ln_b/merge_w`` on every stage but the last.

``forward`` computes in the precisions of ``vbench.reference.vit``
(``f32``, ``f32_bf16dot``, and the fake-quantized ``int8`` / ``int4``,
whose sites are the patch embedding, each block's q/k/v input, attention
output projection and two MLP products, each merge and the head).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from vbench.reference.vit import QUANT_MAX, _Dots, _gelu, _layer_norm

# the additive score of a pair of tokens from different regions of a
# shifted window (the published code adds -100)
MASKED = -1e30


def _geom(g: Mapping[str, Any]):
    return (int(g["image"]), int(g["patch"]), int(g["embed_dim"]),
            tuple(int(d) for d in g["depths"]),
            tuple(int(h) for h in g["heads"]), int(g["window"]),
            float(g["mlp_ratio"]), int(g["n_classes"]),
            float(g.get("ln_eps", 1e-5)))


def program_geometry(cfg) -> Dict[str, Any]:
    """The sizes of the program's Swin config ``cfg`` under the keys of a
    configuration's ``geometry``: what the harness holds the
    configuration to."""
    return {"image": cfg.image, "patch": cfg.patch,
            "embed_dim": cfg.embed_dim, "depths": list(cfg.depths),
            "heads": list(cfg.heads), "window": cfg.window,
            "mlp_ratio": cfg.mlp_ratio, "n_classes": cfg.n_classes}


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, geom):
    _, patch, c, depths, heads, win, ratio, n_cls, _ = geom
    pdim = patch * patch * 3
    keys = iter(jax.random.split(key, 8 + 16 * sum(depths)
                                 + 4 * len(depths)))

    def mat(d_in, shape):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            d_in)

    def vec(shape, base, spread):
        return base + spread * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

    params = {"patch_embed": mat(pdim, (pdim, c)),
              "pe_ln_w": vec((c,), 1.0, 0.05),
              "pe_ln_b": vec((c,), 0.0, 0.02)}
    stages = []
    d = c
    for s, (depth, h) in enumerate(zip(depths, heads)):
        m = int(d * ratio)
        dh = d // h
        blocks = []
        for _ in range(depth):
            blocks.append({
                "ln1_w": vec((d,), 1.0, 0.05), "ln1_b": vec((d,), 0.0, 0.02),
                "wq": mat(d, (h, d, dh)), "wk": mat(d, (h, d, dh)),
                "wv": mat(d, (h, d, dh)), "w_msa": mat(d, (d, d)),
                # a trained table spreads over units, not the 0.02 of
                # its initialisation; at 1.0 the bias moves the scores
                "rel_bias": vec(((2 * win - 1) ** 2, h), 0.0, 1.0),
                "ln2_w": vec((d,), 1.0, 0.05), "ln2_b": vec((d,), 0.0, 0.02),
                "w_up": mat(d, (d, m)), "b_up": vec((m,), 0.0, 0.02),
                "w_down": mat(m, (m, d)), "b_down": vec((d,), 0.0, 0.02),
            })
        stage = {"blocks": blocks}
        if s < len(depths) - 1:
            stage["merge_ln_w"] = vec((4 * d,), 1.0, 0.05)
            stage["merge_ln_b"] = vec((4 * d,), 0.0, 0.02)
            stage["merge_w"] = mat(4 * d, (4 * d, 2 * d))
            d *= 2
        stages.append(stage)
    params["stages"] = stages
    params["ln_f_w"] = vec((d,), 1.0, 0.05)
    params["ln_f_b"] = vec((d,), 0.0, 0.02)
    params["head"] = mat(d, (d, n_cls))
    return params


def init_params(key, g: Mapping[str, Any]):
    """The model's weights from a PRNG key, made on the default device."""
    return _init(key, _geom(g))


def relative_index(win: int) -> np.ndarray:
    """(n, n) index into the (2w-1)^2 bias table of each pair of tokens of
    a window, tokens numbered row by row: the pair's offset
    (dy, dx) in [-(w-1), w-1]^2, shifted to be non-negative, row-major."""
    n = win * win
    idx = np.zeros((n, n), np.int32)
    for i in range(n):
        for j in range(n):
            dy = i // win - j // win
            dx = i % win - j % win
            idx[i, j] = (dy + win - 1) * (2 * win - 1) + (dx + win - 1)
    return idx


def region_mask(side: int, win: int, shift: int) -> np.ndarray:
    """(nW, n, n) additive mask of a feature map of ``side`` x ``side``
    tokens after the cyclic shift by ``shift``: the paper's nine regions
    (rows and columns each cut at side - win and side - shift), and
    ``MASKED`` between tokens of different regions in one window.
    Windows are numbered row by row, as ``_windows`` lays them out."""
    cuts = (0, side - win, side - shift, side)
    label = np.zeros((side, side), np.int32)
    for a in range(3):
        for b in range(3):
            label[cuts[a]:cuts[a + 1], cuts[b]:cuts[b + 1]] = 3 * a + b
    k = side // win
    out = np.zeros((k * k, win * win, win * win), np.float32)
    for wy in range(k):
        for wx in range(k):
            ids = label[wy * win:(wy + 1) * win,
                        wx * win:(wx + 1) * win].reshape(-1)
            out[wy * k + wx] = np.where(ids[:, None] == ids[None, :], 0.0,
                                        MASKED)
    return out


def _windows(x, win: int):
    """(B, S, S, C) -> (B, nW, win*win, C), windows row by row."""
    b, s, _, c = x.shape
    k = s // win
    x = x.reshape(b, k, win, k, win, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, k * k, win * win, c)


def _unwindows(x, win: int, side: int):
    b, _, _, c = x.shape
    k = side // win
    x = x.reshape(b, k, k, win, win, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, side, side, c)


def _block(x, bp, win: int, shift: int, eps: float, dots: _Dots, site: str):
    """One Swin block on (B, S, S, C): (shifted) window attention with its
    residual, then the MLP with its residual."""
    b, side, _, d = x.shape
    h, _, dh = bp["wq"].shape
    z = _layer_norm(x, bp["ln1_w"], bp["ln1_b"], eps)
    if shift:
        z = jnp.roll(z, (-shift, -shift), axis=(1, 2))
    zw = _windows(z, win)                                 # (B, nW, n, C)
    n_w, n = zw.shape[1], zw.shape[2]
    wqkv = jnp.concatenate([bp["wq"], bp["wk"], bp["wv"]], axis=0)
    wqkv = wqkv.transpose(1, 0, 2).reshape(d, 3 * h * dh)
    qkv = dots(zw, wqkv, f"{site}.qkv_in").reshape(b, n_w, n, 3, h, dh)
    q, k, v = (qkv[..., i, :, :].transpose(0, 1, 3, 2, 4) for i in range(3))
    s = dots.einsum("bwhqe,bwhke->bwhqk", q, k) / math.sqrt(dh)
    bias = bp["rel_bias"][relative_index(win)]            # (n, n, H)
    s = s + bias.transpose(2, 0, 1)[None, None]
    if shift:
        s = s + jnp.asarray(region_mask(side, win, shift))[None, :, None]
    s = s - jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s)
    pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
    a = dots.einsum("bwhqk,bwhke->bwhqe", pr, v)
    a = a.transpose(0, 1, 3, 2, 4).reshape(b, n_w, n, h * dh)
    o = _unwindows(dots(a, bp["w_msa"], f"{site}.w_msa"), win, side)
    if shift:
        o = jnp.roll(o, (shift, shift), axis=(1, 2))
    x = x + o
    z2 = _layer_norm(x, bp["ln2_w"], bp["ln2_b"], eps)
    hid = _gelu(dots(z2, bp["w_up"], f"{site}.w_up") + bp["b_up"])
    return x + dots(hid, bp["w_down"], f"{site}.w_down") + bp["b_down"]


def _merge(x, stage, eps: float, dots: _Dots, site: str):
    """(B, S, S, C) -> (B, S/2, S/2, 2C): each 2x2 neighbourhood's tokens
    concatenated at (row, column) offsets (0,0), (0,1), (1,0), (1,1),
    then layer norm and the linear map."""
    cat = jnp.concatenate([x[:, 0::2, 0::2], x[:, 0::2, 1::2],
                           x[:, 1::2, 0::2], x[:, 1::2, 1::2]], axis=-1)
    cat = _layer_norm(cat, stage["merge_ln_w"], stage["merge_ln_b"], eps)
    return dots(cat, stage["merge_w"], site)


def _forward(params, images, geom, dots: _Dots):
    image, patch, _, _, _, win, _, _, eps = geom
    p = params
    side = image // patch
    b = images.shape[0]
    x = images.astype(jnp.float32).reshape(b, side, patch, side, patch, 3)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, side, side,
                                              patch * patch * 3)
    x = dots(x, p["patch_embed"], "patch_embed")
    x = _layer_norm(x, p["pe_ln_w"], p["pe_ln_b"], eps)
    for s, stage in enumerate(p["stages"]):
        w = min(win, side)
        for i, bp in enumerate(stage["blocks"]):
            # shifted windows on every second block, where the stage's
            # map is larger than one window (the published rule)
            shift = w // 2 if i % 2 == 1 and side > w else 0
            x = _block(x, bp, w, shift, eps, dots, f"s{s}.b{i}")
        if "merge_w" in stage:
            x = _merge(x, stage, eps, dots, f"s{s}.merge")
            side //= 2
    x = _layer_norm(x, p["ln_f_w"], p["ln_f_b"], eps)
    return dots(jnp.mean(x, axis=(1, 2)), p["head"],
                "head").astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(precision: str, geom):
    if precision in QUANT_MAX:
        return jax.jit(lambda p, x, s: _forward(p, x, geom,
                                                _Dots(precision, s)))
    return jax.jit(lambda p, x: _forward(p, x, geom, _Dots(precision)))


def calibrate(params, calib_images, g, precision: str) -> Dict[str, jax.Array]:
    """Per-site activation scales for a fake-quantized ``precision``, from
    the reference's own float32 pass over ``calib_images``."""
    dots = _Dots(precision)
    _forward(params, jnp.asarray(calib_images), _geom(g), dots)
    q = QUANT_MAX[precision]
    return {k: jnp.maximum(v, 1e-8) / q for k, v in dots.amax.items()}


def forward(params, images, g: Mapping[str, Any], *, precision: str = "f32",
            act_scales: Optional[Mapping[str, jax.Array]] = None,
            block: int = 16) -> np.ndarray:
    """Logits (B, n_classes) of ``images`` (B, H, W, 3), computed in blocks
    of ``block`` images so that it fits beside whatever else is held."""
    fn = _jitted(precision, _geom(g))
    out = []
    images = np.asarray(images)
    for i in range(0, len(images), block):
        chunk = jnp.asarray(images[i:i + block])
        if precision in QUANT_MAX:
            out.append(np.asarray(fn(params, chunk, dict(act_scales))))
        else:
            out.append(np.asarray(fn(params, chunk)))
    return np.concatenate(out)
