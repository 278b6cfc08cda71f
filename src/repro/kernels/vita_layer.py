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

Windowed (Swin W-MSA) layers fuse too: the executor folds windows into the
batch axis, and as LN, the concat, the residuals and the MLP are per-token
maps, the layer commutes with the fold.  The float windowed calls, one
layer or a group, have bodies and grids of their own: Wb windows a step.

VMEM a grid step: x/out blocks (two buffers each) + z/acc (N·D each) + a
head's weights (3·D·Dh + Dh·D) + both MLP matrices (2·D·M, int8 in PTQ) +
the head's Q/K/V/S.  Windowed, N is Wb·n and the (Wb·n, M) MLP hidden and
Wb windows' scores join, and a group's Wb·n·D carry: `window_block` keeps
a layer's step under 16 MiB by `_window_step_bytes` (Swin-T, Wb 8: 4–11 MiB).
"""

from __future__ import annotations

import contextlib
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
                       o_ref, z_ref, acc_ref, *, scale: float, n_heads: int):
    # One image's N tokens a grid step, on the (B, H) grid of the module
    # docstring, with no relative bias or region mask (ViT, DeiT).  A
    # windowed (Swin) layer runs `_vita_layer_windowed_kernel` on a grid
    # of its own, where a block of Wb windows shares each step and its
    # head's weights: its head matmuls see Wb·n rows where these see N.
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
    ``bias`` (H, n, n) + ``mask`` (nW, n, n) as `vita_msa_batched` does —
    the caller folds windows into the batch axis and reverses after — and
    runs `window_block` windows a grid step (`_vita_layer_windowed`)."""
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
    if bias is not None:
        # Swin W-MSA: a block of windows a grid step, on a body and grid
        # of its own (`window_block` sizes the block); the specs above
        # serve the unwindowed call alone.
        return _vita_layer_windowed(x, wq, wk, wv, wmsa_h, ln1_w, ln1_b,
                                    ln2_w, ln2_b, w_up, b_up, w_down,
                                    b_down, bias, mask, interpret=interpret)

    kernel = functools.partial(_vita_layer_kernel, scale=dh ** -0.5,
                               n_heads=h)
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
# windowed float kernel (Swin W-MSA): a block of windows per grid step
# ---------------------------------------------------------------------------

# Rows (windows × tokens) a windowed grid step aims for, and the scoped
# VMEM a step may take, by `_window_step_bytes` (16 MiB: the limit the
# TPU compiler gives a kernel by default); see `window_block`.
WINDOW_ROWS = 392
WINDOW_VMEM_BYTES = 16 << 20


def _window_step_bytes(wb: int, n: int, d: int, m: int) -> int:
    """Scoped VMEM of a windowed grid step of ``wb`` windows, ``n`` tokens
    each: per window sixteen (n, 128-lane) float32 tiles of the head's
    q/k/v, scores and probabilities, and per token 8·(D + M) bytes of
    x/out blocks, z/acc scratch and the MLP hidden.  An upper bound of
    what the v5e compiler asks at Swin-T's four stages (its ask at
    Wb 8: 4.7, 4.2, 5.5 and 11.2 MiB; at Wb 16: 9.8, 8.8, 11.9, 26.4)."""
    tile = 4 * -(-n // 8) * 8 * 128
    return wb * (16 * tile + 8 * n * (d + m))


def window_block(n_windows: int, n_w: int, n: int, d: int, m: int) -> int:
    """Windows per grid step of the windowed `vita_layer`, from the shapes
    it is called with: ``n_windows`` = B·nW folded windows, ``n_w`` of
    them an image, ``n`` tokens a window, width ``d``, MLP hidden ``m``.

    The block divides the folded count and lines up with the masks'
    period (it divides ``n_w``, or ``n_w`` divides it), so every block
    reads its masks as one (Wb, n, n) block.  Of those, the smallest whose
    Wb·n rows reach `WINDOW_ROWS`, else the largest; either within
    `WINDOW_VMEM_BYTES`.
    """
    fits = [wb for wb in range(1, n_windows + 1)
            if n_windows % wb == 0 and (n_w % wb == 0 or wb % n_w == 0)
            and _window_step_bytes(wb, n, d, m) <= WINDOW_VMEM_BYTES]
    reach = [wb for wb in fits if wb * n >= WINDOW_ROWS]
    return min(reach) if reach else max(fits, default=1)


def window_grid_steps(n_windows: int, n_w: int, n: int, d: int, h: int,
                      m: int) -> int:
    """Grid steps of one windowed `vita_layer` call: (B·nW / Wb) × H."""
    return n_windows // window_block(n_windows, n_w, n, d, m) * h


_step_tallies: list = []


@contextlib.contextmanager
def tally_window_steps():
    """Count the grid steps of the windowed `vita_layer` calls traced
    inside: yields a one-item list that holds the running count."""
    tally = [0]
    _step_tallies.append(tally)
    try:
        yield tally
    finally:
        _step_tallies.remove(tally)


def note_window_call(x, wq, w_up, mask) -> None:
    """Add one `vita_layer` or `vita_layer_group` call's grid steps to the
    open tallies (none for an unwindowed call, ``mask`` None).  The caller
    notes each call as it makes it: jit traces the kernel's wrapper once
    per shape, and a forward calls it with one shape for several layers."""
    if mask is None:
        return
    bw, n, d = x.shape
    layers = wq.shape[0] if wq.ndim == 4 else 1     # (L, H, D, Dh): a group
    steps = layers * window_grid_steps(bw, mask.shape[0], n, d,
                                       wq.shape[-3], w_up.shape[-1])
    for tally in _step_tallies:
        tally[0] += steps


def _window_head(z, wq, wk, wv, wmsa, bias, m_ref, *, wb: int, n: int,
                 scale: float):
    """One head's concat-slice contribution for a block of ``wb`` windows
    of ``n`` tokens: z (Wb·n, D) -> (Wb·n, D).  Engine 2 runs window by
    window: one (n, n) score tile each, the head's relative ``bias``
    shared by all of them, each window's region mask (row w of
    ``m_ref``) its own.  `_softmax_av` on each window keeps the per-layer
    and the layer-group kernels bit for bit alike (a batched q·kᵀ rounds
    differently on the CPU)."""
    def project(w):
        return jnp.dot(z, w, preferred_element_type=jnp.float32
                       ).reshape(wb, n, -1)

    q, k, v = project(wq), project(wk), project(wv)
    sa = jnp.stack([_softmax_av(q[w], k[w], v[w], scale=scale,
                                extra=bias + m_ref[w]) for w in range(wb)])
    return jnp.dot(sa.reshape(wb * n, -1), wmsa,
                   preferred_element_type=jnp.float32)


def _mlp_tail(h1, ln2_w, ln2_b, w_up, b_up, w_down, b_down):
    """LN2 -> MLP -> residual on the post-MSA rows h1."""
    z2 = _ln(h1, ln2_w, ln2_b)
    hid = jax.nn.gelu(jnp.dot(z2, w_up, preferred_element_type=jnp.float32)
                      + b_up.astype(jnp.float32))
    return h1 + jnp.dot(hid, w_down, preferred_element_type=jnp.float32) \
        + b_down.astype(jnp.float32)


def _window_rows(x_ref):
    """A (Wb, n, D) block of windows as one (Wb·n, D) float32 matrix (a
    bfloat16 reshape would split the packed sublane tile)."""
    wb, n, d = x_ref.shape
    return x_ref[...].astype(jnp.float32).reshape(wb * n, d)


def _vita_layer_windowed_kernel(x_ref, wq_ref, wk_ref, wv_ref, wmsa_ref,
                                ln1w_ref, ln1b_ref, ln2w_ref, ln2b_ref,
                                wup_ref, bup_ref, wdown_ref, bdown_ref,
                                b_ref, m_ref, o_ref, z_ref, acc_ref, *,
                                scale: float, n_heads: int):
    j = pl.program_id(1)
    wb, n, d = x_ref.shape

    @pl.when(j == 0)
    def _init():
        z_ref[...] = _ln(_window_rows(x_ref), ln1w_ref[...], ln1b_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _window_head(z_ref[...], wq_ref[0], wk_ref[0],
                                 wv_ref[0], wmsa_ref[0], b_ref[0], m_ref,
                                 wb=wb, n=n, scale=scale)

    @pl.when(j == n_heads - 1)
    def _tail():
        y = _mlp_tail(_window_rows(x_ref) + acc_ref[...], ln2w_ref[...],
                      ln2b_ref[...], wup_ref[...], bup_ref[...],
                      wdown_ref[...], bdown_ref[...])
        o_ref[...] = y.reshape(wb, n, d).astype(o_ref.dtype)


def _window_masks(mask, wb: int):
    """The (nW, n, n) region masks as float32 blocks of ``wb``, tiled to
    ``wb`` where nW < Wb, and the number of such blocks: window i of the
    fold takes mask i % nW, so block i takes mask block i % (nW / Wb)."""
    mask = mask.astype(jnp.float32)
    if mask.shape[0] < wb:
        mask = jnp.tile(mask, (wb // mask.shape[0], 1, 1))
    return mask, mask.shape[0] // wb


def _vita_layer_windowed(x, wq, wk, wv, wmsa_h, ln1_w, ln1_b, ln2_w, ln2_b,
                         w_up, b_up, w_down, b_down, bias, mask, *,
                         interpret: bool, wb: int | None = None):
    """`vita_layer` in windowed mode at ``wb`` windows per grid step
    (`window_block`'s by default): grid (B·nW / Wb, H), heads minor as in
    the unwindowed call."""
    bw, n, d = x.shape
    h, _, dh = wq.shape
    m = w_up.shape[1]
    n_w = mask.shape[0]
    if wb is None:
        wb = window_block(bw, n_w, n, d, m)
    if bw % wb or (n_w % wb and wb % n_w):
        raise ValueError(f"{wb} windows a step neither divide {bw} folded "
                         f"windows nor line up with {n_w} masks")
    mask, n_mb = _window_masks(mask, wb)
    w_spec = pl.BlockSpec((1, d, dh), lambda i, j: (j, 0, 0))
    vec_d = pl.BlockSpec((d,), lambda i, j: (0,))
    x_spec = pl.BlockSpec((wb, n, d), lambda i, j: (i, 0, 0))
    kernel = functools.partial(_vita_layer_windowed_kernel,
                               scale=dh ** -0.5, n_heads=h)
    return pl.pallas_call(
        kernel,
        grid=(bw // wb, h),
        in_specs=[
            x_spec,
            w_spec, w_spec, w_spec,
            pl.BlockSpec((1, dh, d), lambda i, j: (j, 0, 0)),   # concat
            vec_d, vec_d, vec_d, vec_d,
            pl.BlockSpec((d, m), lambda i, j: (0, 0)),          # w_up
            pl.BlockSpec((m,), lambda i, j: (0,)),
            pl.BlockSpec((m, d), lambda i, j: (0, 0)),          # w_down
            vec_d,
            pl.BlockSpec((1, n, n), lambda i, j: (j, 0, 0)),    # rel bias
            pl.BlockSpec((wb, n, n), lambda i, j: (i % n_mb, 0, 0)),
        ],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((bw, n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((wb * n, d), jnp.float32),   # z
                        pltpu.VMEM((wb * n, d), jnp.float32)],  # acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, wq, wk, wv, wmsa_h, ln1_w, ln1_b, ln2_w, ln2_b,
      w_up, b_up, w_down, b_down, bias.astype(jnp.float32), mask)


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
                             o_ref, y_ref, z_ref, acc_ref, *, scale: float,
                             n_layers: int, n_heads: int):
    # One image a grid step, unwindowed; a windowed group runs
    # `_vita_layer_group_windowed_kernel`.
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
    sa = _softmax_av(q, k, v, scale=scale)
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
    if bias is not None:
        return _vita_layer_group_windowed(x, wq, wk, wv, wmsa_h, ln1_w,
                                          ln1_b, ln2_w, ln2_b, w_up, b_up,
                                          w_down, b_down, bias, mask,
                                          interpret=interpret)
    kernel = functools.partial(_vita_layer_group_kernel, scale=dh ** -0.5,
                               n_layers=n_l, n_heads=h)
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


def _vita_layer_group_windowed_kernel(x_ref, wq_ref, wk_ref, wv_ref,
                                      wmsa_ref, ln1w_ref, ln1b_ref,
                                      ln2w_ref, ln2b_ref, wup_ref, bup_ref,
                                      wdown_ref, bdown_ref, b_ref, m_ref,
                                      o_ref, y_ref, z_ref, acc_ref, *,
                                      scale: float, n_layers: int,
                                      n_heads: int):
    # The per-layer windowed step with the group's layer axis: the block's
    # (Wb·n, D) activation rides in y_ref across the L layers.
    l = pl.program_id(1)
    j = pl.program_id(2)
    wb, n, d = x_ref.shape

    @pl.when((l == 0) & (j == 0))
    def _load():
        y_ref[...] = _window_rows(x_ref)

    @pl.when(j == 0)
    def _init():
        z_ref[...] = _ln(y_ref[...], ln1w_ref[0], ln1b_ref[0])
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _window_head(z_ref[...], wq_ref[0, 0], wk_ref[0, 0],
                                 wv_ref[0, 0], wmsa_ref[0, 0], b_ref[0, 0],
                                 m_ref, wb=wb, n=n, scale=scale)

    @pl.when(j == n_heads - 1)
    def _tail():
        y_ref[...] = _mlp_tail(y_ref[...] + acc_ref[...], ln2w_ref[0],
                               ln2b_ref[0], wup_ref[0], bup_ref[0],
                               wdown_ref[0], bdown_ref[0])

    @pl.when((l == n_layers - 1) & (j == n_heads - 1))
    def _out():
        o_ref[...] = y_ref[...].reshape(wb, n, d).astype(o_ref.dtype)


def _vita_layer_group_windowed(x, wq, wk, wv, wmsa_h, ln1_w, ln1_b, ln2_w,
                               ln2_b, w_up, b_up, w_down, b_down, bias,
                               mask, *, interpret: bool):
    """`vita_layer_group` in windowed mode: the per-layer call's block of
    `window_block` windows a grid step, on grid (B·nW / Wb, L, H)."""
    bw, n, d = x.shape
    n_l, h, _, dh = wq.shape
    m = w_up.shape[2]
    wb = window_block(bw, mask.shape[0], n, d, m)
    mask, n_mb = _window_masks(mask, wb)
    w_spec = pl.BlockSpec((1, 1, d, dh), lambda i, l, j: (l, j, 0, 0))
    vec_d, vec_m = _layer_vec_spec(d), _layer_vec_spec(m)
    x_spec = pl.BlockSpec((wb, n, d), lambda i, l, j: (i, 0, 0))
    kernel = functools.partial(_vita_layer_group_windowed_kernel,
                               scale=dh ** -0.5, n_layers=n_l, n_heads=h)
    return pl.pallas_call(
        kernel,
        grid=(bw // wb, n_l, h),
        in_specs=[
            x_spec,
            w_spec, w_spec, w_spec,
            pl.BlockSpec((1, 1, dh, d), lambda i, l, j: (l, j, 0, 0)),
            vec_d, vec_d, vec_d, vec_d,
            pl.BlockSpec((1, d, m), lambda i, l, j: (l, 0, 0)),
            vec_m,
            pl.BlockSpec((1, m, d), lambda i, l, j: (l, 0, 0)),
            vec_d,
            pl.BlockSpec((1, 1, n, n), lambda i, l, j: (l, j, 0, 0)),
            pl.BlockSpec((wb, n, n), lambda i, l, j: (i % n_mb, 0, 0)),
        ],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct((bw, n, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((wb * n, d), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, wq, wk, wv, wmsa_h, *_layer_vecs(ln1_w, ln1_b, ln2_w, ln2_b),
      w_up, *_layer_vecs(b_up), w_down, *_layer_vecs(b_down),
      bias.astype(jnp.float32), mask)


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
