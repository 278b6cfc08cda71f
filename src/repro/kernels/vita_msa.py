"""Paper-faithful fused per-head MSA Pallas kernels (ViT-scale).

This is the direct TPU transcription of ViTA's two-engine head pipeline
(Sec. III-B2, Fig. 2/4) for vision-transformer sequence lengths (N ~ 49-256,
where one head's *entire* working set fits in VMEM):

  grid = (batch, heads)            # head-level coarse-grained pipeline
  per step (b, h):
    engine-1 analogue: Q = z_b @ Wq[h]; K = z_b @ Wk[h]; V = z_b @ Wv[h]
    engine-2 analogue: SA[b, h] = softmax(Q K^T / sqrt(Dh)) @ V

* z_b (image b's layer input) is the stationary operand: heads iterate in
  the minor grid dimension, so Pallas keeps the z block resident across all
  H steps of one image — ViTA's input-stationary dataflow.
* Wq/Wk/Wv for the next (b, h) step are DMA'd into VMEM while the current
  head computes (Pallas grid pipelining) — the double-buffered
  weight-column BRAM ping-pong, carried across the batch loop (head-0
  weights stream back in while image b's last head computes).
* Only ONE head's Q/K/V/S ever exists on-chip, exactly the paper's memory
  argument for head-wise computation.

The int8 variant is the PTQ inference mode of Sec. III-A through a real
kernel: int8 x int8 -> int32 projections on the MXU with the fused
activation x per-(head, out-channel) requantization of `int8_matmul`, and
the softmax/AV stage kept in fp32 (the paper's dedicated high-precision
softmax unit).

Windowed (Swin) attention runs on the SAME grid — ViTA's Sec. IV control
argument that W-MSA is "the regular MSA performed repeatedly over these
windows": the control program folds the windows into the batch axis, so the
grid becomes (batch * n_windows, heads) with no kernel change to the
dataflow.  Two per-window additive terms ride along:

  * ``bias`` (H, n, n)   — relative position bias, selected by the head
    grid index (same for every window);
  * ``mask`` (nW, n, n)  — shifted-window region mask (0 / -1e30),
    selected by ``i % nW`` (window identity of batch-axis step i).

For LM-scale sequence lengths, `head_attention.flash_attention` is the
streaming generalization (row-granular online softmax).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def f32_dot(a, b, *, interpret: bool = False):
    """MXU matmul with an f32 accumulator.  The CPU interpreter has no
    bf16 x bf16 -> f32 dot, so when interpreting, bf16 operands widen to
    f32 first; that is exact (a bf16 product fits in f32's mantissa), and
    the chip still multiplies bf16 natively."""
    if interpret:
        a, b = (x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x
                for x in (a, b))
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def softmax_av(q, k, v, *, scale: float, out_dtype=jnp.float32,
               extra=None, interpret: bool = False):
    """Engine 2 core: QK^T (PE block 4) -> stable softmax -> S.V (PE
    block 5).  The one in-kernel definition — `vita_layer` imports it."""
    s = f32_dot(q, k.T, interpret=interpret) * scale
    if extra is not None:
        s = s + extra
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return f32_dot(p.astype(out_dtype), v.astype(out_dtype),
                   interpret=interpret)


def _attend(q, k, v, o_ref, *, scale: float, out_dtype, extra=None,
            interpret: bool = False):
    o_ref[0, 0] = softmax_av(q, k, v, scale=scale, out_dtype=out_dtype,
                             extra=extra, interpret=interpret
                             ).astype(o_ref.dtype)


def _vita_msa_kernel(z_ref, wq_ref, wk_ref, wv_ref, *rest, scale: float,
                     windowed: bool, has_qkv_bias: bool, interpret: bool):
    rest = list(rest)
    o_ref = rest.pop()
    qb = rest.pop(0)[:, 0] if has_qkv_bias else None       # (3, 1, Dh)
    extra = rest[0][0] + rest[1][0] if windowed else None
    z = z_ref[0]
    # Engine 1: per-head projections (PE blocks 1-3).
    q = f32_dot(z, wq_ref[0], interpret=interpret)
    k = f32_dot(z, wk_ref[0], interpret=interpret)
    v = f32_dot(z, wv_ref[0], interpret=interpret)
    if qb is not None:
        q = q + qb[0]
        k = k + qb[1]
        v = v + qb[2]
    _attend(q, k, v, o_ref, scale=scale, out_dtype=z.dtype, extra=extra,
            interpret=interpret)


def _qkv_bias_operand(qkv_bias: jax.Array):
    """(3, H, Dh) stacked per-head Q/K/V bias -> a (3, H, 1, Dh) operand
    whose per-head block (3, 1, 1, Dh) keeps its last two dims whole, as
    the TPU tiling rule asks; selected by head index."""
    three, h, dh = qkv_bias.shape
    spec = pl.BlockSpec((three, 1, 1, dh), lambda i, j: (0, j, 0, 0))
    return spec, qkv_bias.astype(jnp.float32).reshape(three, h, 1, dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_msa_batched(z: jax.Array, wq: jax.Array, wk: jax.Array,
                     wv: jax.Array, bias: jax.Array = None,
                     mask: jax.Array = None, qkv_bias: jax.Array = None, *,
                     interpret: bool = False) -> jax.Array:
    """z: (B, N, D); wq/wk/wv: (H, D, Dh) -> (B, H, N, Dh).

    One pallas_call covers the whole batch: grid (B, H), z stationary per
    image, head weights double-buffered across the batch loop.

    Windowed (Swin) mode: the caller folds windows into the batch axis
    (B = images * nW) and passes ``bias`` (H, N, N) — per-head relative
    position bias — and ``mask`` (nW, N, N) — additive shifted-window region
    mask, window identity recovered as ``i % nW``.  Both or neither.

    ``qkv_bias`` (3, H, Dh) optionally adds a per-head projection bias
    (Q = zWq + b_q[h], ...) — the slot reference checkpoints' ``qkv.bias``
    folds into.  Default None keeps the bias-free ViTA datapath.
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask "
                         "(pass a zero mask for unshifted blocks)")
    b, n, d = z.shape
    h, _, dh = wq.shape
    w_spec = pl.BlockSpec((1, d, dh), lambda i, j: (j, 0, 0))
    z_spec = pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0))   # z stationary
    in_specs = [z_spec, w_spec, w_spec, w_spec]
    operands = [z, wq, wk, wv]
    if qkv_bias is not None:
        spec, qkv_bias = _qkv_bias_operand(qkv_bias)
        in_specs.append(spec)
        operands.append(qkv_bias)
    if bias is not None:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, n, n), lambda i, j: (j, 0, 0)),       # rel bias
            pl.BlockSpec((1, n, n), lambda i, j: (i % n_w, 0, 0)),  # region
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_msa_kernel, scale=dh ** -0.5,
                               windowed=bias is not None,
                               has_qkv_bias=qkv_bias is not None,
                               interpret=interpret)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n, dh), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, n, dh), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_msa(z: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
             *, interpret: bool = False) -> jax.Array:
    """z: (N, D); wq/wk/wv: (H, D, Dh) -> (H, N, Dh) per-head attention.

    Single-image convenience wrapper over the batched (B, H) grid.
    """
    return vita_msa_batched(z[None], wq, wk, wv, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# int8 PTQ variant (Sec. III-A requant units fused into engine 1)
# ---------------------------------------------------------------------------


def _int8_proj(z, w_ref, ws_ref, xs):
    # MXU-native int8 x int8 -> int32 with the requant fused in VMEM.
    acc = jax.lax.dot_general(
        z, w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (xs * ws_ref[0])


def _vita_msa_int8_kernel(z_ref, wq_ref, wk_ref, wv_ref, xs_ref,
                          qs_ref, ks_ref, vs_ref, *rest, scale: float,
                          windowed: bool, has_qkv_bias: bool):
    rest = list(rest)
    o_ref = rest.pop()
    qb = rest.pop(0)[:, 0] if has_qkv_bias else None    # (3, 1, Dh) fp32
    extra = rest[0][0] + rest[1][0] if windowed else None
    z = z_ref[0]                         # (N, D) int8
    xs = xs_ref[0, 0]                    # per-tensor activation scale
    q = _int8_proj(z, wq_ref, qs_ref, xs)
    k = _int8_proj(z, wk_ref, ks_ref, xs)
    v = _int8_proj(z, wv_ref, vs_ref, xs)
    # The Q/K/V bias (like the window bias/mask) joins AFTER the requant, in
    # fp32 — ViTA keeps the softmax inputs high precision.
    if qb is not None:
        q = q + qb[0]
        k = k + qb[1]
        v = v + qb[2]
    _attend(q, k, v, o_ref, scale=scale, out_dtype=jnp.float32, extra=extra)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_msa_int8(z_q: jax.Array, wq_q: jax.Array, wk_q: jax.Array,
                  wv_q: jax.Array, x_scale: jax.Array,
                  wq_scale: jax.Array, wk_scale: jax.Array,
                  wv_scale: jax.Array, bias: jax.Array = None,
                  mask: jax.Array = None, qkv_bias: jax.Array = None, *,
                  interpret: bool = False) -> jax.Array:
    """Fused int8 per-head MSA over the whole batch.

    z_q: (B, N, D) int8; w*_q: (H, D, Dh) int8; x_scale: scalar float32;
    w*_scale: (H, Dh) per-(head, out-channel) float32.  Returns
    (B, H, N, Dh) float32 (attention runs in fp32 after the requant).

    Windowed mode mirrors `vita_msa_batched`: windows folded into the batch
    axis, ``bias`` (H, N, N) + ``mask`` (nW, N, N) added in fp32 before the
    softmax.  ``qkv_bias`` (3, H, Dh) is the optional float per-head
    projection bias, added after the requant (default None: bias-free).
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask")
    b, n, d = z_q.shape
    h, _, dh = wq_q.shape
    x_scale = jnp.asarray(x_scale, jnp.float32).reshape(1, 1)
    w_spec = pl.BlockSpec((1, d, dh), lambda i, j: (j, 0, 0))
    # Per-head scales ride as (H, 1, Dh): a (1, 1, Dh) block keeps the
    # last two dims whole, which the TPU tiling rule asks of a block.
    s_spec = pl.BlockSpec((1, 1, dh), lambda i, j: (j, 0, 0))
    in_specs = [
        pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0)),       # z stationary
        w_spec, w_spec, w_spec,
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        s_spec, s_spec, s_spec,
    ]
    operands = [z_q, wq_q, wk_q, wv_q, x_scale] + [
        ws.astype(jnp.float32).reshape(h, 1, dh)
        for ws in (wq_scale, wk_scale, wv_scale)]
    if qkv_bias is not None:
        spec, qkv_bias = _qkv_bias_operand(qkv_bias)
        in_specs.append(spec)
        operands.append(qkv_bias)
    if bias is not None:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, n, n), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, j: (i % n_w, 0, 0)),
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_msa_int8_kernel, scale=dh ** -0.5,
                               windowed=bias is not None,
                               has_qkv_bias=qkv_bias is not None)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, n, dh), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, n, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
