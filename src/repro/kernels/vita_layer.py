"""Fused encoder-layer Pallas kernel — the cross-phase pipeline of Sec. III.

`vita_msa.py` transcribes ViTA's head-level pipeline *within* the MSA
phase; this module extends it *across* the msa→concat→mlp phase boundary,
which is where the paper's ~90% hardware utilization actually comes from
(Sec. III, Table IV): the accelerator never drains the datapath between
the MSA of a layer and its MLP, it streams the concat projection and the
MLP behind the head pipeline.  The schedule executor used to synchronize
at every `Phase` — each encoder layer was ≥2 independent `pallas_call`s
with the activation bouncing through HBM in between.  Here one kernel
runs the ENTIRE encoder layer per grid step stream:

  grid = (batch, heads)                     # same (B, H) grid as vita_msa
  per step (b, h):
    z        = LN1(x_b)                     # dedicated LN unit
    SA_h     = softmax(z Wq[h] (z Wk[h])^T / sqrt(Dh) [+bias+mask]) z Wv[h]
    acc_b   += SA_h @ W_msa[h·Dh:(h+1)·Dh]  # head-sliced concat projection:
                                            # head h's concat column starts
                                            # the moment SA_h exists — the
                                            # paper's concat-behind-heads
                                            # overlap, as an accumulator
  at h == H-1 (the tail of image b's head pipeline):
    x'       = x_b + acc_b                  # MSA residual
    y        = x' + MLP(LN2(x'))            # both MLP matmuls, in-VMEM
    out_b    = y

Nothing between LN1 and the layer output ever leaves the kernel grid: no
per-phase HBM round-trip for the (N, D) activation, no separate concat
matmul, no second kernel launch for the MLP.

The int8 variant is the PTQ inference mode with the requantization chain
fused in: activations are re-quantized *between stages inside the kernel*
(z → int8 for Q/K/V, SA → int8 for the concat columns, LN2 out → int8 for
the up-projection, GELU out → int8 for the down-projection) using the
frozen per-site calibration scales of `core/quant.py` — exactly the scale
chain the unfused executor applies, so fused int8 == unfused int8 up to
float-accumulation order.  The int32 concat accumulator is requantized
once at the tail (per-output-channel w_msa scales are head-invariant, so
head slices may accumulate in int32).

Windowed (Swin W-MSA) layers fuse too: the control program folds windows
into the batch axis exactly as for `vita_msa`, and because LN, the concat
projection, the residuals and the MLP are all per-token maps, the WHOLE
layer commutes with the window permutation — the kernel runs on the
(B·nW, n, C) layout and the executor reverses the fold afterwards.

VMEM budget per grid step: x/acc/out tiles (3·N·D) + one head's weights
(3·D·Dh + Dh·D) + the full MLP matrices (2·D·M, int8 in PTQ mode) + the
per-step Q/K/V/S head working set.  Sized for the edge regime the paper
targets (D ≤ ~384 comfortably; ViT-B at fp32 would need hidden chunking —
see `fused_mlp.py` — before running un-interpreted on real hardware).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared single definitions: the LN math (also behind `ops.layer_norm`)
# and the engine-2 softmax·V core of the per-phase MSA kernels.
from .ref import layer_norm_ref as _ln
from .vita_msa import softmax_av as _softmax_av

_INT8_MAX = 127.0


def _quant(x, scale):
    """Symmetric int8 quantization with a frozen per-site scale."""
    return jnp.clip(jnp.round(x / scale), -_INT8_MAX, _INT8_MAX
                    ).astype(jnp.int8)


def _int8_dot(a_q, b_q):
    return jax.lax.dot_general(a_q, b_q, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# float kernel
# ---------------------------------------------------------------------------


def _vita_layer_kernel(x_ref, wq_ref, wk_ref, wv_ref, wmsa_ref,
                       ln1w_ref, ln1b_ref, ln2w_ref, ln2b_ref,
                       wup_ref, bup_ref, wdown_ref, bdown_ref,
                       *rest, scale: float, n_heads: int, windowed: bool):
    if windowed:
        b_ref, m_ref, o_ref, z_ref, acc_ref = rest
        extra = b_ref[0] + m_ref[0]
    else:
        o_ref, z_ref, acc_ref = rest
        extra = None
    j = pl.program_id(1)
    x = x_ref[0]

    @pl.when(j == 0)
    def _init():
        # z is the stationary engine-1 input: LN once per image, resident
        # in VMEM across all H head steps (ViTA's input-stationary rule).
        z_ref[...] = _ln(x, ln1w_ref[...], ln1b_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...]
    q = jnp.dot(z, wq_ref[0], preferred_element_type=jnp.float32)
    k = jnp.dot(z, wk_ref[0], preferred_element_type=jnp.float32)
    v = jnp.dot(z, wv_ref[0], preferred_element_type=jnp.float32)
    sa = _softmax_av(q, k, v, scale=scale, extra=extra)
    # Head h's slice of the concat projection starts as soon as SA_h exists.
    acc_ref[...] += jnp.dot(sa, wmsa_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_heads - 1)
    def _tail():
        h1 = x.astype(jnp.float32) + acc_ref[...]
        z2 = _ln(h1, ln2w_ref[...], ln2b_ref[...])
        hid = jax.nn.gelu(
            jnp.dot(z2, wup_ref[...], preferred_element_type=jnp.float32)
            + bup_ref[...].astype(jnp.float32))
        y = h1 + jnp.dot(hid, wdown_ref[...],
                         preferred_element_type=jnp.float32) \
            + bdown_ref[...].astype(jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_layer(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
               w_msa: jax.Array, ln1_w: jax.Array, ln1_b: jax.Array,
               ln2_w: jax.Array, ln2_b: jax.Array, w_up: jax.Array,
               b_up: jax.Array, w_down: jax.Array, b_down: jax.Array,
               bias: jax.Array = None, mask: jax.Array = None, *,
               interpret: bool = False) -> jax.Array:
    """One fused encoder layer: x (B, N, D) -> (B, N, D).

    wq/wk/wv: (H, D, Dh); w_msa: (D, D) (head-major rows, sliced per head
    inside); w_up: (D, M); w_down: (M, D).  Windowed (Swin) mode takes
    ``bias`` (H, n, n) + ``mask`` (nW, n, n) exactly as `vita_msa_batched`
    — the caller folds windows into the batch axis and reverses after.
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask")
    b, n, d = x.shape
    h, _, dh = wq.shape
    m = w_up.shape[1]
    wmsa_h = w_msa.reshape(h, dh, d)       # head-major concat slices
    w_spec = pl.BlockSpec((1, d, dh), lambda i, j: (j, 0, 0))
    vec_d = pl.BlockSpec((d,), lambda i, j: (0,))
    in_specs = [
        pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0)),    # x stationary
        w_spec, w_spec, w_spec,
        pl.BlockSpec((1, dh, d), lambda i, j: (j, 0, 0)),   # concat slice
        vec_d, vec_d, vec_d, vec_d,
        pl.BlockSpec((d, m), lambda i, j: (0, 0)),          # w_up resident
        pl.BlockSpec((m,), lambda i, j: (0,)),
        pl.BlockSpec((m, d), lambda i, j: (0, 0)),          # w_down resident
        vec_d,
    ]
    operands = [x, wq, wk, wv, wmsa_h, ln1_w, ln1_b, ln2_w, ln2_b,
                w_up, b_up, w_down, b_down]
    windowed = bias is not None
    if windowed:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, n, n), lambda i, j: (j, 0, 0)),       # rel bias
            pl.BlockSpec((1, n, n), lambda i, j: (i % n_w, 0, 0)),  # region
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_layer_kernel, scale=dh ** -0.5,
                               n_heads=h, windowed=windowed)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32),   # z (stationary)
                        pltpu.VMEM((n, d), jnp.float32)],  # concat acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# layer-group megakernel (float): L stacked layers, one pallas_call
# ---------------------------------------------------------------------------


def _layer_vec_spec(width: int) -> pl.BlockSpec:
    """Layer l's row of an (L, 1, width) per-layer vector stack.  The unit
    middle axis keeps the block's last two dims whole, as the TPU tiling
    rule asks of a block (a (1, width) block of (L, width) is refused)."""
    return pl.BlockSpec((1, 1, width), lambda i, l, j: (l, 0, 0))


def _layer_vecs(*vecs):
    """(L, width) per-layer vectors -> the (L, 1, width) operand layout."""
    return [v.reshape(v.shape[0], 1, -1) for v in vecs]


def _vita_layer_group_kernel(x_ref, wq_ref, wk_ref, wv_ref, wmsa_ref,
                             ln1w_ref, ln1b_ref, ln2w_ref, ln2b_ref,
                             wup_ref, bup_ref, wdown_ref, bdown_ref,
                             *rest, scale: float, n_layers: int,
                             n_heads: int, windowed: bool):
    if windowed:
        b_ref, m_ref, o_ref, y_ref, z_ref, acc_ref = rest
        extra = b_ref[0, 0] + m_ref[0]
    else:
        o_ref, y_ref, z_ref, acc_ref = rest
        extra = None
    l = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((l == 0) & (j == 0))
    def _load():
        # The running activation lives in VMEM for the WHOLE group: layer
        # boundaries stop being kernel launches + HBM round-trips.
        y_ref[...] = x_ref[0].astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        z_ref[...] = _ln(y_ref[...], ln1w_ref[0], ln1b_ref[0])
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...]
    # Layer l's per-head MSA; while this step computes, Pallas prefetches
    # the NEXT (l, j) step's weight blocks — at the MLP tail (j == H-1)
    # that is layer l+1's Q/K/V, the cross-layer weight streaming ViTA's
    # overlap map (Sec. III) keeps the datapath busy with.
    q = jnp.dot(z, wq_ref[0, 0], preferred_element_type=jnp.float32)
    k = jnp.dot(z, wk_ref[0, 0], preferred_element_type=jnp.float32)
    v = jnp.dot(z, wv_ref[0, 0], preferred_element_type=jnp.float32)
    sa = _softmax_av(q, k, v, scale=scale, extra=extra)
    acc_ref[...] += jnp.dot(sa, wmsa_ref[0, 0],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_heads - 1)
    def _tail():
        h1 = y_ref[...] + acc_ref[...]
        z2 = _ln(h1, ln2w_ref[0], ln2b_ref[0])
        hid = jax.nn.gelu(
            jnp.dot(z2, wup_ref[0], preferred_element_type=jnp.float32)
            + bup_ref[0].astype(jnp.float32))
        y_ref[...] = h1 + jnp.dot(hid, wdown_ref[0],
                                  preferred_element_type=jnp.float32) \
            + bdown_ref[0].astype(jnp.float32)

    @pl.when((l == n_layers - 1) & (j == n_heads - 1))
    def _out():
        o_ref[0] = y_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_layer_group(x: jax.Array, wq: jax.Array, wk: jax.Array,
                     wv: jax.Array, w_msa: jax.Array, ln1_w: jax.Array,
                     ln1_b: jax.Array, ln2_w: jax.Array, ln2_b: jax.Array,
                     w_up: jax.Array, b_up: jax.Array, w_down: jax.Array,
                     b_down: jax.Array, bias: jax.Array = None,
                     mask: jax.Array = None, *,
                     interpret: bool = False) -> jax.Array:
    """L fused encoder layers in ONE pallas_call: x (B, N, D) -> (B, N, D).

    The per-layer weight pytrees stack into leading-axis operands —
    wq/wk/wv: (L, H, D, Dh); w_msa: (L, D, D); LN vectors: (L, D);
    w_up: (L, D, M); w_down: (L, M, D) — and the grid grows a layer axis:
    ``grid = (B, L, H)`` with the layer and head axes ``arbitrary``
    (sequential per image).  The running (N, D) activation is carried in
    a VMEM scratch across all L·H steps, so a layer boundary costs one
    grid step instead of a kernel launch, and the revolving-buffer
    prefetch streams layer l+1's weights during layer l's tail.

    Windowed (Swin) mode takes ``bias`` (L, H, n, n) — stacked per layer —
    and a SHARED ``mask`` (nW, n, n): group members have one window/shift
    by the grouping pass's compatibility rule, so the caller folds windows
    once for the whole group.
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask")
    b, n, d = x.shape
    n_l, h, _, dh = wq.shape
    m = w_up.shape[2]
    wmsa_h = w_msa.reshape(n_l, h, dh, d)  # head-major concat slices
    w_spec = pl.BlockSpec((1, 1, d, dh), lambda i, l, j: (l, j, 0, 0))
    vec_d, vec_m = _layer_vec_spec(d), _layer_vec_spec(m)
    in_specs = [
        pl.BlockSpec((1, n, d), lambda i, l, j: (i, 0, 0)),   # x (l==0 only)
        w_spec, w_spec, w_spec,
        pl.BlockSpec((1, 1, dh, d), lambda i, l, j: (l, j, 0, 0)),
        vec_d, vec_d, vec_d, vec_d,
        pl.BlockSpec((1, d, m), lambda i, l, j: (l, 0, 0)),   # w_up[l]
        vec_m,
        pl.BlockSpec((1, m, d), lambda i, l, j: (l, 0, 0)),   # w_down[l]
        vec_d,
    ]
    operands = [x, wq, wk, wv, wmsa_h,
                *_layer_vecs(ln1_w, ln1_b, ln2_w, ln2_b),
                w_up, *_layer_vecs(b_up), w_down, *_layer_vecs(b_down)]
    windowed = bias is not None
    if windowed:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, 1, n, n), lambda i, l, j: (l, j, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, l, j: (i % n_w, 0, 0)),
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_layer_group_kernel, scale=dh ** -0.5,
                               n_layers=n_l, n_heads=h, windowed=windowed)
    return pl.pallas_call(
        kernel,
        grid=(b, n_l, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), lambda i, l, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32),   # y (carry)
                        pltpu.VMEM((n, d), jnp.float32),   # z (stationary)
                        pltpu.VMEM((n, d), jnp.float32)],  # concat acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# int8 PTQ kernel (requant chain fused between stages)
# ---------------------------------------------------------------------------


def _vita_layer_int8_kernel(x_ref, wq_ref, wk_ref, wv_ref, wmsa_ref,
                            acts_ref, qs_ref, ks_ref, vs_ref, msas_ref,
                            ln1w_ref, ln1b_ref, ln2w_ref, ln2b_ref,
                            wup_ref, ups_ref, bup_ref,
                            wdown_ref, downs_ref, bdown_ref,
                            *rest, scale: float, n_heads: int,
                            windowed: bool):
    if windowed:
        b_ref, m_ref, o_ref, zq_ref, acc_ref = rest
        extra = b_ref[0] + m_ref[0]
    else:
        o_ref, zq_ref, acc_ref = rest
        extra = None
    j = pl.program_id(1)
    x = x_ref[0]
    s_qkv = acts_ref[0, 0]
    s_msa = acts_ref[0, 1]

    @pl.when(j == 0)
    def _init():
        # LN + requant once per image; the int8 z stays resident in VMEM
        # across all H head steps (input-stationary, quantized form).
        zq_ref[...] = _quant(_ln(x, ln1w_ref[...], ln1b_ref[...]), s_qkv)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    zq = zq_ref[...]
    # Engine 1: int8 x int8 -> int32 with the per-(head, channel) requant.
    q = _int8_dot(zq, wq_ref[0]).astype(jnp.float32) * (s_qkv * qs_ref[0])
    k = _int8_dot(zq, wk_ref[0]).astype(jnp.float32) * (s_qkv * ks_ref[0])
    v = _int8_dot(zq, wv_ref[0]).astype(jnp.float32) * (s_qkv * vs_ref[0])
    sa = _softmax_av(q, k, v, scale=scale, extra=extra)   # fp32 softmax unit
    # Requantize SA_h and run head h's concat columns in int32; w_msa's
    # per-output-channel scale is head-invariant, so slices accumulate
    # exactly (requantized once at the tail).
    acc_ref[...] += _int8_dot(_quant(sa, s_msa), wmsa_ref[0])

    @pl.when(j == n_heads - 1)
    def _tail():
        s_up = acts_ref[0, 2]
        s_down = acts_ref[0, 3]
        msa_out = acc_ref[...].astype(jnp.float32) * (s_msa * msas_ref[...])
        h1 = x.astype(jnp.float32) + msa_out
        z2q = _quant(_ln(h1, ln2w_ref[...], ln2b_ref[...]), s_up)
        hid = jax.nn.gelu(
            _int8_dot(z2q, wup_ref[...]).astype(jnp.float32)
            * (s_up * ups_ref[...]) + bup_ref[...].astype(jnp.float32))
        y = h1 + _int8_dot(_quant(hid, s_down), wdown_ref[...]
                           ).astype(jnp.float32) \
            * (s_down * downs_ref[...]) + bdown_ref[...].astype(jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_layer_int8(x: jax.Array, wq_q: jax.Array, wk_q: jax.Array,
                    wv_q: jax.Array, wmsa_q: jax.Array, wup_q: jax.Array,
                    wdown_q: jax.Array, act_scales: jax.Array,
                    wq_scale: jax.Array, wk_scale: jax.Array,
                    wv_scale: jax.Array, wmsa_scale: jax.Array,
                    wup_scale: jax.Array, wdown_scale: jax.Array,
                    ln1_w: jax.Array, ln1_b: jax.Array,
                    ln2_w: jax.Array, ln2_b: jax.Array,
                    b_up: jax.Array, b_down: jax.Array,
                    bias: jax.Array = None, mask: jax.Array = None, *,
                    interpret: bool = False) -> jax.Array:
    """Fused int8 encoder layer: x (B, N, D) float32 -> (B, N, D) float32.

    The running activation stream stays float (as in the unfused PTQ
    executor); matmul inputs are requantized in-kernel with the frozen
    ``act_scales`` = [qkv_in, w_msa, w_up, w_down] calibration scales.
    w*_q are int8; w*_scale are per-(head, out-channel) (H, Dh) for QKV
    and per-output-channel (D,)/(M,)/(D,) for the plain matmuls.
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask")
    b, n, d = x.shape
    h, _, dh = wq_q.shape
    m = wup_q.shape[1]
    wmsa_h = wmsa_q.reshape(h, dh, d)
    act_scales = jnp.asarray(act_scales, jnp.float32).reshape(1, 4)
    w_spec = pl.BlockSpec((1, d, dh), lambda i, j: (j, 0, 0))
    # Per-head scales ride as (H, 1, Dh) so a head's block keeps its last
    # two dims whole (the TPU tiling rule).
    s_spec = pl.BlockSpec((1, 1, dh), lambda i, j: (j, 0, 0))
    vec_d = pl.BlockSpec((d,), lambda i, j: (0,))
    vec_m = pl.BlockSpec((m,), lambda i, j: (0,))
    in_specs = [
        pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0)),    # x stationary
        w_spec, w_spec, w_spec,
        pl.BlockSpec((1, dh, d), lambda i, j: (j, 0, 0)),   # concat slice
        pl.BlockSpec((1, 4), lambda i, j: (0, 0)),          # act scales
        s_spec, s_spec, s_spec, vec_d,
        vec_d, vec_d, vec_d, vec_d,
        pl.BlockSpec((d, m), lambda i, j: (0, 0)), vec_m, vec_m,
        pl.BlockSpec((m, d), lambda i, j: (0, 0)), vec_d, vec_d,
    ]
    operands = [x, wq_q, wk_q, wv_q, wmsa_h, act_scales,
                *(ws.astype(jnp.float32).reshape(h, 1, dh)
                  for ws in (wq_scale, wk_scale, wv_scale)),
                wmsa_scale.astype(jnp.float32).reshape(d),
                ln1_w, ln1_b, ln2_w, ln2_b,
                wup_q, wup_scale.astype(jnp.float32).reshape(m), b_up,
                wdown_q, wdown_scale.astype(jnp.float32).reshape(d), b_down]
    windowed = bias is not None
    if windowed:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, n, n), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, j: (i % n_w, 0, 0)),
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_layer_int8_kernel, scale=dh ** -0.5,
                               n_heads=h, windowed=windowed)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.int8),      # zq (stationary)
                        pltpu.VMEM((n, d), jnp.int32)],    # concat acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# int8 layer-group megakernel
# ---------------------------------------------------------------------------


def _vita_layer_group_int8_kernel(x_ref, wq_ref, wk_ref, wv_ref, wmsa_ref,
                                  acts_ref, qs_ref, ks_ref, vs_ref,
                                  msas_ref, ln1w_ref, ln1b_ref,
                                  ln2w_ref, ln2b_ref,
                                  wup_ref, ups_ref, bup_ref,
                                  wdown_ref, downs_ref, bdown_ref,
                                  *rest, scale: float, n_layers: int,
                                  n_heads: int, windowed: bool):
    if windowed:
        b_ref, m_ref, o_ref, y_ref, zq_ref, acc_ref = rest
        extra = b_ref[0, 0] + m_ref[0]
    else:
        o_ref, y_ref, zq_ref, acc_ref = rest
        extra = None
    l = pl.program_id(1)
    j = pl.program_id(2)
    s_qkv = acts_ref[0, 0, 0]
    s_msa = acts_ref[0, 0, 1]

    @pl.when((l == 0) & (j == 0))
    def _load():
        y_ref[...] = x_ref[0].astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        # Each layer requantizes at ITS frozen per-site scale (the
        # (1, 1, 4) acts block is indexed by the layer axis).
        zq_ref[...] = _quant(_ln(y_ref[...], ln1w_ref[0], ln1b_ref[0]),
                             s_qkv)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    zq = zq_ref[...]
    q = _int8_dot(zq, wq_ref[0, 0]).astype(jnp.float32) \
        * (s_qkv * qs_ref[0, 0])
    k = _int8_dot(zq, wk_ref[0, 0]).astype(jnp.float32) \
        * (s_qkv * ks_ref[0, 0])
    v = _int8_dot(zq, wv_ref[0, 0]).astype(jnp.float32) \
        * (s_qkv * vs_ref[0, 0])
    sa = _softmax_av(q, k, v, scale=scale, extra=extra)
    acc_ref[...] += _int8_dot(_quant(sa, s_msa), wmsa_ref[0, 0])

    @pl.when(j == n_heads - 1)
    def _tail():
        s_up = acts_ref[0, 0, 2]
        s_down = acts_ref[0, 0, 3]
        msa_out = acc_ref[...].astype(jnp.float32) * (s_msa * msas_ref[0])
        h1 = y_ref[...] + msa_out
        z2q = _quant(_ln(h1, ln2w_ref[0], ln2b_ref[0]), s_up)
        hid = jax.nn.gelu(
            _int8_dot(z2q, wup_ref[0]).astype(jnp.float32)
            * (s_up * ups_ref[0]) + bup_ref[0].astype(jnp.float32))
        y_ref[...] = h1 + _int8_dot(_quant(hid, s_down), wdown_ref[0]
                                    ).astype(jnp.float32) \
            * (s_down * downs_ref[0]) + bdown_ref[0].astype(jnp.float32)

    @pl.when((l == n_layers - 1) & (j == n_heads - 1))
    def _out():
        o_ref[0] = y_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def vita_layer_group_int8(x: jax.Array, wq_q: jax.Array, wk_q: jax.Array,
                          wv_q: jax.Array, wmsa_q: jax.Array,
                          wup_q: jax.Array, wdown_q: jax.Array,
                          act_scales: jax.Array, wq_scale: jax.Array,
                          wk_scale: jax.Array, wv_scale: jax.Array,
                          wmsa_scale: jax.Array, wup_scale: jax.Array,
                          wdown_scale: jax.Array, ln1_w: jax.Array,
                          ln1_b: jax.Array, ln2_w: jax.Array,
                          ln2_b: jax.Array, b_up: jax.Array,
                          b_down: jax.Array, bias: jax.Array = None,
                          mask: jax.Array = None, *,
                          interpret: bool = False) -> jax.Array:
    """L fused int8 encoder layers in one pallas_call (the int8 twin of
    `vita_layer_group`): x (B, N, D) float32 -> (B, N, D) float32.

    Stacked operands: w*_q (L, H, D, Dh) int8 QKV / (L, D, D), (L, D, M),
    (L, M, D) matmuls; ``act_scales`` (L, 4) = each member's frozen
    [qkv_in, w_msa, w_up, w_down] calibration scales; weight scales
    (L, H, Dh) for QKV, (L, D)/(L, M)/(L, D) per-channel.  The float
    carry requantizes inside the grid at layer l's own scales, so grouped
    int8 == per-layer fused int8 == unfused int8 bit-exact.
    """
    if (bias is None) != (mask is None):
        raise ValueError("windowed mode needs both bias and mask")
    b, n, d = x.shape
    n_l, h, _, dh = wq_q.shape
    m = wup_q.shape[2]
    wmsa_h = wmsa_q.reshape(n_l, h, dh, d)
    act_scales = jnp.asarray(act_scales, jnp.float32).reshape(n_l, 1, 4)
    w_spec = pl.BlockSpec((1, 1, d, dh), lambda i, l, j: (l, j, 0, 0))
    # (L, H, 1, Dh) per-head scales and (L, 1, X) per-layer vectors: the
    # unit axes keep every block's last two dims whole (TPU tiling rule).
    s_spec = pl.BlockSpec((1, 1, 1, dh), lambda i, l, j: (l, j, 0, 0))
    vec_d, vec_m = _layer_vec_spec(d), _layer_vec_spec(m)
    in_specs = [
        pl.BlockSpec((1, n, d), lambda i, l, j: (i, 0, 0)),   # x (l==0 only)
        w_spec, w_spec, w_spec,
        pl.BlockSpec((1, 1, dh, d), lambda i, l, j: (l, j, 0, 0)),
        _layer_vec_spec(4),                                   # act scales[l]
        s_spec, s_spec, s_spec, vec_d,
        vec_d, vec_d, vec_d, vec_d,
        pl.BlockSpec((1, d, m), lambda i, l, j: (l, 0, 0)), vec_m, vec_m,
        pl.BlockSpec((1, m, d), lambda i, l, j: (l, 0, 0)), vec_d, vec_d,
    ]
    f32 = jnp.float32
    operands = [x, wq_q, wk_q, wv_q, wmsa_h, act_scales,
                *(ws.astype(f32).reshape(n_l, h, 1, dh)
                  for ws in (wq_scale, wk_scale, wv_scale)),
                *_layer_vecs(wmsa_scale.astype(f32).reshape(n_l, d),
                             ln1_w, ln1_b, ln2_w, ln2_b),
                wup_q,
                *_layer_vecs(wup_scale.astype(f32).reshape(n_l, m), b_up),
                wdown_q,
                *_layer_vecs(wdown_scale.astype(f32).reshape(n_l, d),
                             b_down)]
    windowed = bias is not None
    if windowed:
        n_w = mask.shape[0]
        in_specs += [
            pl.BlockSpec((1, 1, n, n), lambda i, l, j: (l, j, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i, l, j: (i % n_w, 0, 0)),
        ]
        operands += [bias.astype(jnp.float32), mask.astype(jnp.float32)]
    kernel = functools.partial(_vita_layer_group_int8_kernel,
                               scale=dh ** -0.5, n_layers=n_l, n_heads=h,
                               windowed=windowed)
    return pl.pallas_call(
        kernel,
        grid=(b, n_l, h),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), lambda i, l, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32),   # y (carry)
                        pltpu.VMEM((n, d), jnp.int8),      # zq (stationary)
                        pltpu.VMEM((n, d), jnp.int32)],    # concat acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
