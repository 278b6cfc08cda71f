"""Plain reference of the ViT / DeiT classifier, and its weights.

Written from the published description (Dosovitskiy et al. 2021;
Touvron et al. 2021) in straightforward ``jax.numpy``, with the
departures each configuration file lists under ``departures``: the
tokens are mean-pooled instead of read from a class token, and the patch
embedding, the q/k/v projections and the attention output projection
carry no bias.  It imports nothing of the system under test.

``init_params`` makes the weights from a seed, on the device, in one
jitted call, in the pytree layout the served program reads:
per-head ``wq/wk/wv`` stacks of shape (H, D, Dh), the attention output
projection ``w_msa`` (H*Dh, D) with head-major rows, and the MLP.

``program_geometry`` reads the program's model config back in the
configuration's geometry keys.

``forward`` is the reference.  ``precision`` picks its arithmetic:

* ``"f32"``: float32 throughout, every matmul at ``highest`` precision;
* ``"f32_bf16dot"``: float32 throughout, but every matmul takes its
  operands rounded to bfloat16 and accumulates in float32, which is what
  a float32 matmul at the TPU's default precision computes;
* ``"int8"``: the control for a configuration whose matmuls take
  bfloat16 operands, post-training quantization at 8 bits (weights
  symmetric per output channel, activations symmetric per tensor at the
  six sites of the program's int8 mode, with scales from a calibration
  pass of the reference itself);
* ``"int4"``: the control for an int8 configuration, the same recipe at
  4 bits.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the largest quantized magnitude of each fake-quantized precision
QUANT_MAX = {"int8": 127.0, "int4": 7.0}


def _geom(g: Mapping[str, Any]):
    return (int(g["image"]), int(g["patch"]), int(g["dim"]), int(g["heads"]),
            int(g["layers"]), int(g["mlp_hidden"]), int(g["n_classes"]))


def program_geometry(cfg) -> Dict[str, Any]:
    """The sizes of the program's model ``cfg`` under the keys of a
    configuration's ``geometry``: what the harness holds the
    configuration to."""
    return {"image": cfg.image, "patch": cfg.patch, "dim": cfg.dim,
            "heads": cfg.heads, "layers": cfg.layers,
            "mlp_hidden": cfg.mlp_hidden, "n_classes": cfg.n_classes}


def tokens(g: Mapping[str, Any]) -> int:
    return (int(g["image"]) // int(g["patch"])) ** 2


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, geom):
    image, patch, d, h, n_layers, m, n_cls = geom
    n = (image // patch) ** 2
    dh = d // h
    pdim = patch * patch * 3
    keys = iter(jax.random.split(key, 5 + 12 * n_layers))

    def mat(d_in, shape):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(
            d_in)

    def vec(shape, base, spread):
        return base + spread * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

    layers = []
    for _ in range(n_layers):
        layers.append({
            "ln1_w": vec((d,), 1.0, 0.05), "ln1_b": vec((d,), 0.0, 0.02),
            "wq": mat(d, (h, d, dh)), "wk": mat(d, (h, d, dh)),
            "wv": mat(d, (h, d, dh)), "w_msa": mat(d, (d, d)),
            "ln2_w": vec((d,), 1.0, 0.05), "ln2_b": vec((d,), 0.0, 0.02),
            "w_up": mat(d, (d, m)), "b_up": vec((m,), 0.0, 0.02),
            "w_down": mat(m, (m, d)), "b_down": vec((d,), 0.0, 0.02),
        })
    return {
        "patch_embed": mat(pdim, (pdim, d)),
        "pos_embed": vec((n, d), 0.0, 0.02),
        "layers": layers,
        "ln_f_w": vec((d,), 1.0, 0.05), "ln_f_b": vec((d,), 0.0, 0.02),
        "head": mat(d, (d, n_cls)),
    }


def init_params(key, g: Mapping[str, Any]):
    """The model's weights from a PRNG key, made on the default device."""
    return _init(key, _geom(g))


def patchify(images: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, 3) -> (B, N, P*P*3): patches in row-major order, each
    flattened as (row in patch, column in patch, channel)."""
    b, hh, ww, c = images.shape
    x = images.reshape(b, hh // patch, patch, ww // patch, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, (hh // patch) * (ww // patch), patch * patch * c)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu(x):
    # tanh form of GELU, as the configurations state
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


class _Dots:
    """The matmul of one precision: f32 at ``highest``, f32 with bfloat16
    operands, or fake quantization at a named site (``amax`` records
    calibration)."""

    def __init__(self, precision: str, act_scales=None):
        self.precision = precision
        self.act_scales = act_scales
        self.amax: Dict[str, jax.Array] = {}

    def einsum(self, spec: str, a, b):
        if self.precision == "f32_bf16dot":
            return jnp.einsum(spec, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def __call__(self, x, w, site: str):
        if self.precision == "f32_bf16dot":
            return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        if self.precision in QUANT_MAX:
            if self.act_scales is None:            # calibration pass
                a = jnp.max(jnp.abs(x))
                self.amax[site] = jnp.maximum(self.amax.get(site, 0.0), a)
                return jnp.matmul(x, w, precision=HIGHEST)
            q = QUANT_MAX[self.precision]
            s_x = self.act_scales[site]
            xq = jnp.clip(jnp.round(x / s_x), -q, q)
            red = tuple(range(w.ndim - 1))
            s_w = jnp.maximum(jnp.max(jnp.abs(w), axis=red, keepdims=True),
                              1e-8) / q
            wq = jnp.clip(jnp.round(w / s_w), -q, q)
            return jnp.matmul(xq, wq, precision=HIGHEST) * s_x * s_w
        return jnp.matmul(x, w, precision=HIGHEST)


def _forward(params, images, g, dots: _Dots):
    image, patch, d, h, n_layers, m, n_cls = _geom(g)
    eps = float(g.get("ln_eps", 1e-5))
    dh = d // h
    p = params
    x = dots(patchify(images.astype(jnp.float32), patch), p["patch_embed"],
             "patch_embed")
    x = x + p["pos_embed"][None]
    b, n, _ = x.shape
    for li, lp in enumerate(p["layers"]):
        z = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
        # the q/k/v projections of all heads read one quantized input
        wqkv = jnp.concatenate([lp["wq"], lp["wk"], lp["wv"]], axis=0)
        wqkv = wqkv.transpose(1, 0, 2).reshape(d, 3 * h * dh)
        qkv = dots(z, wqkv, f"l{li}.qkv_in").reshape(b, n, 3, h, dh)
        q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
        s = dots.einsum("bhqe,bhke->bhqk", q, k)
        s = s / math.sqrt(dh)
        s = s - jnp.max(s, axis=-1, keepdims=True)
        pr = jnp.exp(s)
        pr = pr / jnp.sum(pr, axis=-1, keepdims=True)
        a = dots.einsum("bhqk,bhke->bhqe", pr, v)
        a = a.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
        x = x + dots(a, lp["w_msa"], f"l{li}.w_msa")
        z2 = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], eps)
        hid = _gelu(dots(z2, lp["w_up"], f"l{li}.w_up") + lp["b_up"])
        x = x + dots(hid, lp["w_down"], f"l{li}.w_down") + lp["b_down"]
    x = _layer_norm(x, p["ln_f_w"], p["ln_f_b"], eps)
    return dots(jnp.mean(x, axis=1), p["head"], "head").astype(jnp.float32)


def _geom_key(g):
    return tuple(sorted((k, v) for k, v in g.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _jitted(precision: str, gkey):
    g = dict(gkey)
    if precision in QUANT_MAX:
        return jax.jit(lambda p, x, s: _forward(p, x, g,
                                                _Dots(precision, s)))
    return jax.jit(lambda p, x: _forward(p, x, g, _Dots(precision)))


def calibrate(params, calib_images, g, precision: str) -> Dict[str, jax.Array]:
    """Per-site activation scales for a fake-quantized ``precision``, from
    the reference's own float32 pass over ``calib_images``."""
    dots = _Dots(precision)
    _forward(params, jnp.asarray(calib_images), g, dots)
    q = QUANT_MAX[precision]
    return {k: jnp.maximum(v, 1e-8) / q for k, v in dots.amax.items()}


def forward(params, images, g: Mapping[str, Any], *, precision: str = "f32",
            act_scales: Optional[Mapping[str, jax.Array]] = None,
            block: int = 16) -> np.ndarray:
    """Logits (B, n_classes) of ``images`` (B, H, W, 3), computed in blocks
    of ``block`` images so that it fits beside whatever else is held."""
    fn = _jitted(precision, _geom_key(g))
    out = []
    images = np.asarray(images)
    for i in range(0, len(images), block):
        chunk = jnp.asarray(images[i:i + block])
        if precision in QUANT_MAX:
            out.append(np.asarray(fn(params, chunk, dict(act_scales))))
        else:
            out.append(np.asarray(fn(params, chunk)))
    return np.concatenate(out)
