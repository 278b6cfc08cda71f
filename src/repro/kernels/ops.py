"""Backend dispatch for the compute hot-spots.

Every op has two implementations that compute the same math:
  * ``xla``    — pure jnp (ref.py oracles): the reference the kernels are
                 checked against, and the path for dry-run lowering
                 (cost_analysis sees real FLOPs).
  * ``pallas`` — the TPU kernels: compiled on the TPU, interpreted on the
                 CPU (so CPU tests execute the actual kernel bodies), and
                 refused on any other platform.

Model code calls these entry points; `set_backend` / the ``backend=`` kwarg
selects the path.  Kernel block sizes are chosen here from the shapes
(128-aligned for the MXU) unless overridden.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .fused_mlp import fused_mlp as _fused_mlp_pallas
from .head_attention import decode_attention as _decode_pallas
from .head_attention import flash_attention as _flash_pallas
from .int8_matmul import int8_matmul as _int8_pallas
from .rglru_scan import rglru_scan as _rglru_pallas
from .vita_layer import note_window_call as _note_window_call
from .vita_layer import vita_layer as _vita_layer_pallas
from .vita_layer import vita_layer_int8 as _vita_layer_int8_pallas
from .vita_layer import vita_layer_group as _vita_layer_group_pallas
from .vita_layer import (vita_layer_group_int8
                         as _vita_layer_group_int8_pallas)
from .vita_msa import vita_msa as _vita_msa_pallas
from .vita_msa import vita_msa_batched as _vita_msa_batched_pallas
from .vita_msa import vita_msa_int8 as _vita_msa_int8_pallas

_BACKEND = "xla"
_ON_TPU = None


def on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


def set_backend(name: str) -> None:
    global _BACKEND
    assert name in ("xla", "pallas")
    _BACKEND = name


def get_backend(override: Optional[str] = None) -> str:
    return override or _BACKEND


def _interp() -> bool:
    """Whether to interpret the Pallas kernels: on the CPU only.  On the
    TPU they compile; anywhere else they cannot run, and saying so beats
    silently interpreting them on an accelerator."""
    if on_tpu():
        return False
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"the Pallas kernels compile for the TPU and are interpreted "
            f"on the CPU; platform {platform!r} can run neither "
            f"(use backend='xla')")
    return True


def _pad_to(x: jax.Array, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def mlp(x, w1, w2, b1=None, b2=None, w_gate=None, *, activation="gelu",
        backend: Optional[str] = None,
        block_n: int = 256, block_h: int = 512):
    """Fused (never-materialize-hidden) MLP."""
    if get_backend(backend) == "xla":
        return ref.fused_mlp_ref(x, w1, b1, w2, b2, activation=activation,
                                 w_gate=w_gate)
    n_tokens = 1
    for s in x.shape[:-1]:
        n_tokens *= s
    bn = _largest_divisor(n_tokens, block_n)
    bh = _largest_divisor(w1.shape[1], block_h)
    return _fused_mlp_pallas(x, w1, w2, b1, b2, w_gate,
                             activation=activation, block_n=bn, block_h=bh,
                             interpret=_interp())


def attention(q, k, v, *, causal=True, window=None, q_offset=0,
              backend: Optional[str] = None,
              block_q: int = 128, block_k: int = 128):
    if get_backend(backend) == "xla":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    bq = _largest_divisor(q.shape[2], block_q)
    bk = _largest_divisor(k.shape[2], block_k)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         q_offset=q_offset, block_q=bq, block_k=bk,
                         interpret=_interp())


def decode_attention(q, k_cache, v_cache, lengths, *,
                     backend: Optional[str] = None, block_k: int = 512):
    if get_backend(backend) == "xla":
        b, hq, dh = q.shape
        s = k_cache.shape[2]
        mask_len = lengths
        out = ref.attention_ref(
            q[:, :, None], k_cache, v_cache, causal=False,
            window=None)
        # ref path needs explicit length masking: redo with mask
        _, hkv, _, _ = k_cache.shape
        group = hq // hkv
        kr = jnp.repeat(k_cache, group, axis=1)
        vr = jnp.repeat(v_cache, group, axis=1)
        scores = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                            kr.astype(jnp.float32)) * (dh ** -0.5)
        valid = (jnp.arange(s)[None, None] < mask_len[:, None, None])
        scores = jnp.where(valid, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        return jnp.einsum("bhk,bhkd->bhd", p,
                          vr.astype(jnp.float32)).astype(q.dtype)
    bk = _largest_divisor(k_cache.shape[2], block_k)
    return _decode_pallas(q, k_cache, v_cache, lengths, block_k=bk,
                          interpret=_interp())


def int8_matmul(x_q, w_q, x_scale=None, w_scale=None, *,
                backend: Optional[str] = None, out_dtype=None):
    if get_backend(backend) == "xla":
        return ref.int8_matmul_ref(x_q, w_q, x_scale, w_scale,
                                   out_dtype=out_dtype or
                                   (jnp.int32 if x_scale is None and
                                    w_scale is None else jnp.float32))
    return _int8_pallas(x_q, w_q, x_scale, w_scale, out_dtype=out_dtype,
                        interpret=_interp())


def vita_msa(z, wq, wk, wv, *, backend: Optional[str] = None):
    if get_backend(backend) == "xla":
        return ref.vita_msa_ref(z, wq, wk, wv)
    return _vita_msa_pallas(z, wq, wk, wv, interpret=_interp())


def vita_msa_batched(z, wq, wk, wv, bias=None, mask=None, qkv_bias=None, *,
                     backend: Optional[str] = None):
    """Whole-batch per-head MSA: (B, N, D) -> (B, H, N, Dh), one kernel.

    ``bias`` (H, N, N) / ``mask`` (nW, N, N) select the windowed (Swin)
    mode — windows folded into the batch axis by the control program.
    ``qkv_bias`` (3, H, Dh): optional per-head projection bias.
    """
    if get_backend(backend) == "xla":
        return ref.vita_msa_batched_ref(z, wq, wk, wv, bias, mask, qkv_bias)
    return _vita_msa_batched_pallas(z, wq, wk, wv, bias, mask, qkv_bias,
                                    interpret=_interp())


def vita_msa_int8(z_q, wq_q, wk_q, wv_q, x_scale, wq_scale, wk_scale,
                  wv_scale, bias=None, mask=None, qkv_bias=None, *,
                  backend: Optional[str] = None):
    """int8 PTQ per-head MSA: (B, N, D) int8 -> (B, H, N, Dh) float32."""
    if get_backend(backend) == "xla":
        return ref.vita_msa_int8_ref(z_q, wq_q, wk_q, wv_q, x_scale,
                                     wq_scale, wk_scale, wv_scale,
                                     bias, mask, qkv_bias)
    return _vita_msa_int8_pallas(z_q, wq_q, wk_q, wv_q, x_scale,
                                 wq_scale, wk_scale, wv_scale, bias, mask,
                                 qkv_bias, interpret=_interp())


def _no_pallas_collectives(msa_axis, mlp_axis):
    if msa_axis is not None or mlp_axis is not None:
        raise NotImplementedError(
            "model-axis all-reduces (msa_axis/mlp_axis) run under "
            "shard_map on the xla backend only; the pallas kernels are "
            "single-device bodies")


def vita_layer_fused(x, wq, wk, wv, w_msa, ln1_w, ln1_b, ln2_w, ln2_b,
                     w_up, b_up, w_down, b_down, bias=None, mask=None, *,
                     backend: Optional[str] = None,
                     msa_axis: Optional[str] = None,
                     mlp_axis: Optional[str] = None):
    """One fused encoder layer (msa -> concat -> mlp): (B, N, D) float ->
    (B, N, D), one kernel chain with no phase-boundary HBM round-trip.
    ``msa_axis``/``mlp_axis`` name the mesh axis to all-reduce the two
    row-parallel partials over on local shards (xla backend only)."""
    if get_backend(backend) == "xla":
        return ref.vita_layer_ref(x, wq, wk, wv, w_msa, ln1_w, ln1_b,
                                  ln2_w, ln2_b, w_up, b_up, w_down, b_down,
                                  bias, mask, msa_axis=msa_axis,
                                  mlp_axis=mlp_axis)
    _no_pallas_collectives(msa_axis, mlp_axis)
    _note_window_call(x, wq, w_up, mask)
    return _vita_layer_pallas(x, wq, wk, wv, w_msa, ln1_w, ln1_b,
                              ln2_w, ln2_b, w_up, b_up, w_down, b_down,
                              bias, mask, interpret=_interp())


def vita_layer_int8(x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q,
                    act_scales, wq_scale, wk_scale, wv_scale, wmsa_scale,
                    wup_scale, wdown_scale, ln1_w, ln1_b, ln2_w, ln2_b,
                    b_up, b_down, bias=None, mask=None, *,
                    backend: Optional[str] = None,
                    msa_axis: Optional[str] = None,
                    mlp_axis: Optional[str] = None):
    """Fused int8 encoder layer with the requant chain (frozen calibration
    ``act_scales`` = [qkv_in, w_msa, w_up, w_down]) inside the kernel."""
    if get_backend(backend) == "xla":
        return ref.vita_layer_int8_ref(
            x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q, act_scales,
            wq_scale, wk_scale, wv_scale, wmsa_scale, wup_scale,
            wdown_scale, ln1_w, ln1_b, ln2_w, ln2_b, b_up, b_down,
            bias, mask, msa_axis=msa_axis, mlp_axis=mlp_axis)
    _no_pallas_collectives(msa_axis, mlp_axis)
    return _vita_layer_int8_pallas(
        x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q, act_scales,
        wq_scale, wk_scale, wv_scale, wmsa_scale, wup_scale, wdown_scale,
        ln1_w, ln1_b, ln2_w, ln2_b, b_up, b_down, bias, mask,
        interpret=_interp())


def vita_layer_group(x, wq, wk, wv, w_msa, ln1_w, ln1_b, ln2_w, ln2_b,
                     w_up, b_up, w_down, b_down, bias=None, mask=None, *,
                     backend: Optional[str] = None,
                     msa_axis: Optional[str] = None,
                     mlp_axis: Optional[str] = None):
    """A layer group (L fused encoder layers, stacked leading-axis
    operands) as ONE kernel chain: (B, N, D) -> (B, N, D).  The pallas
    path runs the (B, L, H)-grid megakernel with the activation carried
    in VMEM across layers; the xla oracle replays the per-layer fused
    oracle, so grouped == per-layer fused by construction there."""
    if get_backend(backend) == "xla":
        return ref.vita_layer_group_ref(x, wq, wk, wv, w_msa, ln1_w, ln1_b,
                                        ln2_w, ln2_b, w_up, b_up, w_down,
                                        b_down, bias, mask,
                                        msa_axis=msa_axis,
                                        mlp_axis=mlp_axis)
    _no_pallas_collectives(msa_axis, mlp_axis)
    _note_window_call(x, wq, w_up, mask)
    return _vita_layer_group_pallas(x, wq, wk, wv, w_msa, ln1_w, ln1_b,
                                    ln2_w, ln2_b, w_up, b_up, w_down,
                                    b_down, bias, mask, interpret=_interp())


def vita_layer_group_int8(x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q,
                          act_scales, wq_scale, wk_scale, wv_scale,
                          wmsa_scale, wup_scale, wdown_scale, ln1_w, ln1_b,
                          ln2_w, ln2_b, b_up, b_down, bias=None, mask=None,
                          *, backend: Optional[str] = None,
                          msa_axis: Optional[str] = None,
                          mlp_axis: Optional[str] = None):
    """int8 layer group: the megakernel with each member's frozen requant
    chain ((L, 4) ``act_scales``, per-layer stacked weight scales)."""
    if get_backend(backend) == "xla":
        return ref.vita_layer_group_int8_ref(
            x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q, act_scales,
            wq_scale, wk_scale, wv_scale, wmsa_scale, wup_scale,
            wdown_scale, ln1_w, ln1_b, ln2_w, ln2_b, b_up, b_down,
            bias, mask, msa_axis=msa_axis, mlp_axis=mlp_axis)
    _no_pallas_collectives(msa_axis, mlp_axis)
    return _vita_layer_group_int8_pallas(
        x, wq_q, wk_q, wv_q, wmsa_q, wup_q, wdown_q, act_scales,
        wq_scale, wk_scale, wv_scale, wmsa_scale, wup_scale, wdown_scale,
        ln1_w, ln1_b, ln2_w, ln2_b, b_up, b_down, bias, mask,
        interpret=_interp())


def linear_recurrence(a, b, *, backend: Optional[str] = None,
                      chunk: int = 256):
    """h_t = a_t * h_{t-1} + b_t along axis 1 (RG-LRU hot loop)."""
    if get_backend(backend) == "xla":
        def combine(l, r):
            a1, b1 = l
            a2, b2 = r
            return a1 * a2, a2 * b1 + b2
        _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
        return h
    return _rglru_pallas(a, b, chunk=chunk, interpret=_interp())


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """fp32-accumulated LayerNorm (ViTA's dedicated LN unit).  The math
    lives once in `ref.layer_norm_ref` — shared by the model layers, the
    schedule executor and the fused layer kernel — this wrapper only
    restores the input dtype."""
    return ref.layer_norm_ref(x, w, b, eps).astype(x.dtype)


def _largest_divisor(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (keeps grids exact)."""
    t = min(target, n)
    while n % t:
        t -= 1
    return t
