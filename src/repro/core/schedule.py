"""ViTA control program (Sec. IV): one datapath, per-model schedules.

The paper's headline claim is that a single fixed PE configuration serves
ViT, DeiT and Swin "with changes solely in our control logic".  This module
is that control logic for the JAX/Pallas reproduction: a *compiler* from a
`core.perfmodel.VisionModelSpec` (the same stage descriptions the analytic
model consumes) to an explicit **phase schedule**, and a single *executor*
that replays any schedule over the shared batched kernels.

Phases (mirroring the accelerator's phase sequencing):

  * ``embed``  — patch-pixel projection (+ LayerNorm for hierarchical
                 models, + learned positional embedding for columnar ones).
                 For TNT it is the dual-stream frontend: pixel sub-patches
                 embed into the inner stream, whose flattened projection
                 seeds the outer stream
  * ``msa``    — LN -> per-head MSA -> concat projection -> residual.
                 Global MSA runs the `(batch, head)`-grid `vita_msa`
                 kernel; windowed/shifted W-MSA runs the SAME grid with
                 windows folded into the batch axis, plus relative position
                 bias and the shifted-window region mask
  * ``mlp``    — LN -> inter-layer fused MLP -> residual
  * ``merge``  — Swin patch merging (2x2 concat -> LN -> linear)
  * ``inner_msa`` / ``inner_mlp`` — TNT pixel-level blocks: the SAME msa /
                 mlp math on the inner stream, whose batch axis carries
                 images x patches (every patch's pixel tokens are one row
                 of the `(batch, head)` grid — the Swin window fold, reused)
  * ``fold``   — TNT re-entry: LN over the flattened pixel tokens of each
                 patch -> linear to the outer dim -> residual into the
                 outer stream
  * ``head``   — final LN -> mean pool -> classifier

A second pass, `fuse_schedule`, collapses each ``msa`` + ``mlp`` pair of
one encoder block (and each ``inner_msa`` + ``inner_mlp`` pair) into a
single fused phase:

  * ``layer`` / ``inner_layer`` — the WHOLE encoder block through one
                 Pallas kernel chain (`kernels/vita_layer.py`): per-head
                 MSA, head-sliced concat accumulation, both LayerNorms and
                 both MLP matmuls without leaving the kernel grid — the
                 cross-phase overlap ViTA's head-level pipelining achieves
                 in hardware (Sec. III; the repeated off-chip activation
                 traffic at phase boundaries is exactly what the design
                 avoids).  Windowed (Swin) blocks fuse too: every per-token
                 map commutes with the window fold, so the executor keeps
                 the fold outside and runs the fused kernel on the
                 (B*nW, n, C) layout.

A third pass (the ``group_size`` knob of the same `fuse_schedule` entry)
collapses *runs* of compatible fused layers into multi-layer megakernel
phases:

  * ``layer_group`` / ``inner_layer_group`` — up to ``group_size``
                 consecutive encoder blocks of one stage through ONE
                 Pallas call: per-layer weight pytrees stack into
                 leading-axis (L, ...) operands and the grid grows a layer
                 axis, so layer i+1's Q/K/V block DMA is prefetched while
                 layer i's MLP tail computes — the remaining half of
                 ViTA's cross-phase overlap (Sec. III), which per-layer
                 fusion stops short of at every block boundary.  Members
                 must share geometry (grid/window/shift/heads) and stage;
                 Swin's alternating shifted blocks and TNT's interleaved
                 inner/fold phases therefore never group, and degenerate
                 groups of one stay plain ``layer`` phases.

Models (`models/vit.py`, `models/swin.py`, `models/tnt.py`) no longer own
forward loops: they emit a spec, `compile_schedule` turns it into phases
(fused by default; ``fused=False`` on the config — or ``--no-fuse`` on the
serving CLI — keeps the per-phase schedule for A/B), and `run_schedule`
executes — float through the Pallas/XLA ops, or int8 PTQ when the params
are `QTensor`s and a calibrator observer is attached.  int8 calibration
always runs the phases unfused (the observer must see every intermediate
activation); frozen-scale inference feeds the recorded per-site scales
into the fused kernel's in-grid requant chain.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.perfmodel import VisionModelSpec
from repro.core.quant import INT8_MAX, QTensor, stack_qtensors
from repro.kernels import ops

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Schedule IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Phase:
    """One control-program step.  ``path`` addresses the param subtree the
    phase reads; ``site`` prefixes its activation-calibration entries."""

    kind: str                      # embed | msa | mlp | merge | head
                                   # | inner_msa | inner_mlp | fold (TNT)
    path: Tuple[Any, ...]
    site: str
    grid: Tuple[int, int]          # (h, w) token grid at phase input
                                   # (inner phases: the pixel sub-grid)
    heads: int = 0                 # SURVIVING heads of this layer under the
                                   # spec's head mask (== architectural count
                                   # when dense).  Execution reads the wq
                                   # shape — which pruning slices to match —
                                   # but `_groupable` compares this field, so
                                   # ragged depth splits layer groups at
                                   # head-count boundaries.
    window: int = 0                # 0 -> global MSA
    shift: int = 0                 # shifted-window offset (W-MSA odd blocks)
    pos_embed: bool = False        # embed: add learned positional embedding
    norm: bool = False             # embed: LayerNorm after projection
    inner_tokens: int = 0          # embed: pixel tokens per patch (TNT; 0
                                   # -> single-stream frontend)
    members: Tuple["Phase", ...] = ()  # layer_group: the grouped per-layer
                                   # phases, in execution order (empty for
                                   # every other kind)


@dataclasses.dataclass(frozen=True)
class Schedule:
    name: str
    image: int
    patch: int
    n_classes: int
    phases: Tuple[Phase, ...]
    backend: Optional[str] = None

    def counts(self) -> dict:
        out: dict = {}
        for p in self.phases:
            out[p.kind] = out.get(p.kind, 0) + 1
        return out


def compile_schedule(spec: VisionModelSpec, *, n_classes: int,
                     backend: Optional[str] = None,
                     hierarchical: Optional[bool] = None) -> Schedule:
    """Compile a model spec into the phase list the executor replays.

    ``hierarchical`` selects the Swin-style layout (windowed MSA with
    relative position bias, ``stages/blocks`` param paths, patch merging);
    by default it is inferred from the spec (multiple stages, windowed
    stages, or patch merging present).
    """
    if hierarchical is None:
        hierarchical = (len(spec.stages) > 1
                        or any(s.n_windows > 1 for s in spec.stages)
                        or any(s.patch_merging for s in spec.stages))
    img_h, img_w, _ = spec.image
    assert img_h == img_w, "control program assumes square images"
    side = img_h // spec.patch
    inner_embed = spec.stages[0].inner_tokens if spec.stages else 0
    assert not (inner_embed and hierarchical), \
        "TNT inner blocks assume the columnar (single-stage) layout"
    phases = [Phase(kind="embed", path=(), site="patch_embed",
                    grid=(side, side), pos_embed=not hierarchical,
                    norm=hierarchical or bool(inner_embed),
                    inner_tokens=inner_embed)]
    flat_layer = 0
    for s_i, st in enumerate(spec.stages):
        exp_side = int(math.isqrt(st.tokens * st.n_windows))
        assert exp_side == side, \
            f"stage {s_i}: token grid {exp_side} != tracked side {side}"
        window = int(math.isqrt(st.tokens)) if hierarchical else 0
        if window:
            assert side % window == 0, \
                f"stage {s_i}: side {side} not divisible by window {window}"
        if st.inner_tokens:
            # the embed phase seeds the inner stream once, so inner blocks
            # can only live in the first (columnar) stage
            assert s_i == 0 and not hierarchical, \
                f"stage {s_i}: inner blocks require the columnar " \
                f"single-stage layout (TNT)"
            mi = int(math.isqrt(st.inner_tokens))
            assert mi * mi == st.inner_tokens, \
                f"stage {s_i}: inner tokens {st.inner_tokens} not square"
        for b_i in range(st.layers):
            if hierarchical:
                path = ("stages", s_i, "blocks", b_i)
                site = f"s{s_i}.b{b_i}"
            else:
                path = ("layers", flat_layer)
                site = f"l{flat_layer}"
                flat_layer += 1
            if st.inner_tokens:
                # TNT: pixel-level blocks run first on the inner stream
                # (batch axis = images x patches — the Swin window fold),
                # then fold back into the outer token at this layer.
                phases.append(Phase(kind="inner_msa",
                                    path=path + ("inner",),
                                    site=f"{site}.inner", grid=(mi, mi),
                                    heads=st.inner_heads))
                phases.append(Phase(kind="inner_mlp",
                                    path=path + ("inner",),
                                    site=f"{site}.inner", grid=(mi, mi)))
                phases.append(Phase(kind="fold", path=path,
                                    site=f"{site}.fold",
                                    grid=(side, side)))
            block = path + ("outer",) if st.inner_tokens else path
            # Swin alternates plain and shifted windows; with a single
            # window the shift is a no-op and is elided (standard Swin).
            shift = (window // 2 if window and b_i % 2 == 1
                     and st.n_windows > 1 else 0)
            phases.append(Phase(kind="msa", path=block, site=site,
                                grid=(side, side),
                                heads=st.layer_heads(b_i),
                                window=window, shift=shift))
            phases.append(Phase(kind="mlp", path=block, site=site,
                                grid=(side, side)))
        if st.patch_merging:
            phases.append(Phase(kind="merge", path=("stages", s_i),
                                site=f"s{s_i}.merge", grid=(side, side)))
            side //= 2
    phases.append(Phase(kind="head", path=(), site="head",
                        grid=(side, side)))
    return Schedule(name=spec.name, image=img_h, patch=spec.patch,
                    n_classes=n_classes, phases=tuple(phases),
                    backend=backend)


# Phase-kind pairs the fusion pass may collapse; a new phase kind is
# fusion-eligible only if it appears here (see docs/MODELS.md, step 2).
FUSABLE_PAIRS = {
    ("msa", "mlp"): "layer",
    ("inner_msa", "inner_mlp"): "inner_layer",
}


# Fused per-block kinds the grouping pass may collapse into multi-layer
# megakernel phases (the FUSABLE_PAIRS analogue one level up); a fused
# kind is grouping-eligible only if it appears here.
GROUPABLE_KINDS = {
    "layer": "layer_group",
    "inner_layer": "inner_layer_group",
}


def _groupable(p: Phase, q: Phase) -> bool:
    """True iff adjacent fused layer ``q`` may join ``p``'s layer group:
    same fused kind, identical geometry (the group kernel performs ONE
    window fold and shares one stacked-operand layout), and the same
    stage — param paths differing only in the trailing block index.  The
    stage rule is what keeps groups from straddling Swin patch-merging or
    TNT fold re-entry even in hand-edited schedules; in compiled ones a
    merge/fold phase already sits between stages."""
    return (q.kind == p.kind
            and q.grid == p.grid and q.window == p.window
            and q.shift == p.shift and q.heads == p.heads
            and len(q.path) == len(p.path)
            and q.path[:-1] == p.path[:-1])


def _group_layers(phases, group_size: int):
    """Collapse maximal runs of compatible fused layers into group phases
    of at most ``group_size`` members (greedy chunking; a leftover run of
    one stays a plain per-layer phase, so every source layer is covered
    exactly once and re-grouping is a no-op)."""
    out = []
    i = 0
    while i < len(phases):
        p = phases[i]
        gkind = GROUPABLE_KINDS.get(p.kind)
        if gkind is None:
            out.append(p)
            i += 1
            continue
        run = [p]
        while (i + len(run) < len(phases) and len(run) < group_size
               and _groupable(p, phases[i + len(run)])):
            run.append(phases[i + len(run)])
        if len(run) == 1:
            out.append(p)
        else:
            out.append(dataclasses.replace(
                p, kind=gkind, members=tuple(run),
                site=f"{run[0].site}..{run[-1].site}"))
        i += len(run)
    return out


def fuse_schedule(sched: Schedule, *, group_size: int = 1) -> Schedule:
    """Collapse adjacent msa->mlp (and inner_msa->inner_mlp) phases of one
    encoder block into single fused ``layer`` / ``inner_layer`` phases.

    Fusion requires the pair to address the same param subtree and
    calibration site (i.e. to be the two halves of ONE block) — schedules
    hand-edited to interleave blocks fall back to per-phase execution.
    The fused phase inherits the msa half's geometry (window/shift/heads),
    which is everything the fused kernel chain needs.

    With ``group_size > 1`` a second sweep collapses runs of compatible
    fused layers (same stage and geometry — see `_groupable`) into
    ``layer_group`` / ``inner_layer_group`` megakernel phases of at most
    ``group_size`` members each.  ``group_size <= 1`` returns exactly the
    per-layer fused schedule, and the pass is idempotent at any size.
    """
    fused = []
    i = 0
    phases = sched.phases
    while i < len(phases):
        p = phases[i]
        nxt = phases[i + 1] if i + 1 < len(phases) else None
        kind = FUSABLE_PAIRS.get((p.kind, nxt.kind)) if nxt else None
        if kind and nxt.path == p.path and nxt.site == p.site \
                and nxt.grid == p.grid:
            fused.append(dataclasses.replace(p, kind=kind))
            i += 2
        else:
            fused.append(p)
            i += 1
    if group_size > 1:
        fused = _group_layers(fused, group_size)
    return dataclasses.replace(sched, phases=tuple(fused))


# ---------------------------------------------------------------------------
# Window geometry (shared by the executor and the Swin reference path)
# ---------------------------------------------------------------------------


def window_partition(x: jax.Array, win: int) -> jax.Array:
    """(B, H, W, C) -> (B * nW, win*win, C); window id = index % nW."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // win, win, w // win, win, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, win * win, c)


def window_reverse(xw: jax.Array, win: int, h: int, w: int) -> jax.Array:
    """Inverse of `window_partition`."""
    b = xw.shape[0] // ((h // win) * (w // win))
    x = xw.reshape(b, h // win, w // win, win, win, -1)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, w, -1)


def pixel_partition(patches: jax.Array, m: int) -> jax.Array:
    """(B, N, P*P*3) patch pixel vectors -> (B*N, m, P*P*3/m) sub-patches.

    The TNT analogue of `window_partition`: each patch's P x P pixel block
    is split into an ms x ms sub-grid (ms = sqrt(m)) of (P/ms)-pixel-square
    sub-patches, and the patches fold into the batch axis — inner row r
    holds patch (r % N) of image (r // N); inner token t is the sub-patch
    at (t // ms, t % ms) of that patch.  Matches the (row, col, channel)
    flattening of `vit.extract_patches`.
    """
    b, n, pd = patches.shape
    ms = int(math.isqrt(m))
    assert ms * ms == m, f"inner token count {m} must be a square"
    p = int(math.isqrt(pd // 3))
    assert p * p * 3 == pd, f"patch dim {pd} is not P*P*3"
    assert p % ms == 0, f"patch side {p} not divisible by sub-grid {ms}"
    ip = p // ms
    x = patches.reshape(b * n, ms, ip, ms, ip, 3)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b * n, m, ip * ip * 3)


@functools.lru_cache(maxsize=None)
def rel_pos_index(win: int) -> np.ndarray:
    """(n, n) gather indices into the (2*win-1)^2 relative-bias table."""
    coords = np.stack(np.meshgrid(np.arange(win), np.arange(win),
                                  indexing="ij")).reshape(2, -1)
    rel = coords[:, :, None] - coords[:, None, :]          # (2, n, n)
    rel = rel.transpose(1, 2, 0) + (win - 1)
    return (rel[..., 0] * (2 * win - 1) + rel[..., 1]).astype(np.int32)


@functools.lru_cache(maxsize=None)
def shifted_window_mask(grid_h: int, grid_w: int, win: int,
                        shift: int) -> np.ndarray:
    """(nW, n, n) additive mask (0 / NEG_INF) for shifted-window attention.

    After a (-shift, -shift) roll, tokens from opposite image edges share a
    window; the standard Swin region labelling keeps attention within the
    9 contiguous source regions.  shift == 0 yields an all-zero mask (the
    kernel's windowed mode always takes a mask, so unshifted blocks pass
    zeros).
    """
    n_w = (grid_h // win) * (grid_w // win)
    n = win * win
    if shift == 0:
        return np.zeros((n_w, n, n), np.float32)
    ids = np.zeros((grid_h, grid_w), np.int32)
    cnt = 0
    for hs in (slice(0, -win), slice(-win, -shift), slice(-shift, None)):
        for ws in (slice(0, -win), slice(-win, -shift), slice(-shift, None)):
            ids[hs, ws] = cnt
            cnt += 1
    idw = ids.reshape(grid_h // win, win, grid_w // win, win)
    idw = idw.transpose(0, 2, 1, 3).reshape(n_w, n)
    same = idw[:, :, None] == idw[:, None, :]
    return np.where(same, 0.0, NEG_INF).astype(np.float32)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


def _subtree(params: Any, path: Tuple[Any, ...]) -> Any:
    node = params
    for k in path:
        node = node[k]
    return node


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Model-axis collective context for `shard_map` execution.

    When the serving mesh carries a ``model`` axis, the executor body runs
    under `shard_map`: every weight arrives as its LOCAL shard (heads /
    MLP columns split, everything else replicated) and the two
    row-parallel contractions per encoder block — the MSA concat
    projection and the MLP down projection — produce partial products
    that must be all-reduced before their residual re-entries.

    ``specs`` is the `distributed.sharding.vision_param_specs` tree for
    the SAME param tree the executor runs on: `reduce_axis` reads the
    block's weight spec back (was its contraction dim sharded over
    ``axis``?), so placement rule and collective can never disagree —
    a block whose heads fell back to replication (H not divisible)
    simply fires no psum.  ``None`` in place of a ShardCtx is the
    single-device / GSPMD data-parallel path: no collectives.
    """

    axis: str
    specs: Any

    def reduce_axis(self, path: Tuple[Any, ...], key: str) -> Optional[str]:
        """Mesh axis to all-reduce over after contracting with weight
        ``key`` of the block at ``path`` — or None when replicated."""
        node = _subtree(self.specs, path)[key]
        if isinstance(node, QTensor):
            node = node.values
        dims = tuple(node)
        return self.axis if dims and dims[0] == self.axis else None

    def psum(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.axis)


def _matmul(x: jax.Array, w: Any, obs, site: str) -> jax.Array:
    """matmul with optional int8 quantization (w: array or QTensor)."""
    if isinstance(w, QTensor):
        scale = obs.observe(site, x)
        xq = jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX
                      ).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, w.values, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * (scale * w.scale)
    return x @ w


def _head_scale(wq: QTensor) -> jax.Array:
    """Per-(head, out-channel) scale (H, 1, Dh) -> the (H, Dh) kernel form."""
    h, _, dh = wq.values.shape
    return wq.scale.reshape(h, dh)


def _per_head_msa(bp: Any, z: jax.Array, obs, site: str,
                  quantized: bool, backend: Optional[str],
                  bias: Optional[jax.Array],
                  mask: Optional[jax.Array]) -> jax.Array:
    """Per-head MSA over a (B', N, C) activation through the shared
    `(batch, head)` grid; B' is images, or images * windows in W-MSA mode.
    Returns (B', N, H·Dh) with heads merged (pre concat-projection) —
    under head-sharded `shard_map` the weight stacks hold only the LOCAL
    heads, so the merged width is theirs (C / model), not C."""
    b, n, c = z.shape
    if quantized:
        scale = obs.observe(f"{site}.qkv_in", z)
        zq = jnp.clip(jnp.round(z / scale), -INT8_MAX, INT8_MAX
                      ).astype(jnp.int8)
        sa = ops.vita_msa_int8(
            zq, bp["wq"].values, bp["wk"].values, bp["wv"].values,
            scale, _head_scale(bp["wq"]), _head_scale(bp["wk"]),
            _head_scale(bp["wv"]), bias, mask, backend=backend)
    else:
        sa = ops.vita_msa_batched(z, bp["wq"], bp["wk"], bp["wv"],
                                  bias, mask, backend=backend)
    h_loc, dh = sa.shape[1], sa.shape[3]
    return sa.transpose(0, 2, 1, 3).reshape(b, n, h_loc * dh
                                            ).astype(z.dtype)


def _window_fold(ph: Phase, x: jax.Array, rel_bias: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A windowed phase's input (B, T, C) shifted by ``ph.shift`` and
    folded into (B*nW, n, C) windows, with the relative-position bias
    gathered from its table (..., (2w-1)^2, H) into (..., H, n, n) and
    the (nW, n, n) region mask.  Runs under the ``fold`` scope, so the
    trace tells this glue from the kernel it feeds."""
    b, _, c = x.shape
    gh, gw = ph.grid
    with jax.named_scope("fold"):
        xs = x.reshape(b, gh, gw, c)
        if ph.shift:
            xs = jnp.roll(xs, (-ph.shift, -ph.shift), axis=(1, 2))
        xw = window_partition(xs, ph.window)
        idx = jnp.asarray(rel_pos_index(ph.window))
        bias = jnp.moveaxis(rel_bias[..., idx, :], -1, -3)
        mask = jnp.asarray(shifted_window_mask(gh, gw, ph.window,
                                               ph.shift))
    return xw, bias, mask


def _window_unfold(ph: Phase, yw: jax.Array, b: int) -> jax.Array:
    """Inverse of `_window_fold`'s fold and shift, to (B, T, C'), under
    the ``unfold`` scope."""
    gh, gw = ph.grid
    with jax.named_scope("unfold"):
        y = window_reverse(yw, ph.window, gh, gw)
        if ph.shift:
            y = jnp.roll(y, (ph.shift, ph.shift), axis=(1, 2))
        return y.reshape(b, gh * gw, y.shape[-1])


def _msa_phase(ph: Phase, bp: Any, x: jax.Array, obs, quantized: bool,
               backend: Optional[str],
               shard: Optional[ShardCtx] = None) -> jax.Array:
    z = ops.layer_norm(x, bp["ln1_w"], bp["ln1_b"])
    if ph.window:
        zw, bias, mask = _window_fold(ph, z, bp["rel_bias"])
        sa = _per_head_msa(bp, zw, obs, ph.site, quantized,
                           backend, bias, mask)
        sa = _window_unfold(ph, sa, x.shape[0])   # local width if sharded
    else:
        sa = _per_head_msa(bp, z, obs, ph.site, quantized,
                           backend, None, None)
    proj = _matmul(sa, bp["w_msa"], obs, f"{ph.site}.w_msa")
    if shard is not None and shard.reduce_axis(ph.path, "w_msa"):
        # Head-sharded block: `sa` holds only the local heads' concat
        # columns, w_msa only their rows — sum the partials over the
        # model axis before the residual.
        proj = shard.psum(proj)
    return x + proj


def _mlp_phase(ph: Phase, bp: Any, x: jax.Array, obs, quantized: bool,
               backend: Optional[str],
               shard: Optional[ShardCtx] = None) -> jax.Array:
    h = ops.layer_norm(x, bp["ln2_w"], bp["ln2_b"])
    # Column-sharded MLP: w_up/b_up hold local hidden columns, w_down the
    # matching rows — psum the down partial, then add b_down exactly once.
    reduce = shard is not None and shard.reduce_axis(ph.path, "w_down")
    if quantized:
        hid = jax.nn.gelu(_matmul(h, bp["w_up"], obs, f"{ph.site}.w_up")
                          + bp["b_up"])
        y = _matmul(hid, bp["w_down"], obs, f"{ph.site}.w_down")
        if reduce:
            y = shard.psum(y)
        y = y + bp["b_down"]
    elif reduce:
        y = shard.psum(ops.mlp(h, bp["w_up"], bp["w_down"], bp["b_up"],
                               None, activation="gelu", backend=backend)) \
            + bp["b_down"]
    else:
        y = ops.mlp(h, bp["w_up"], bp["w_down"], bp["b_up"], bp["b_down"],
                    activation="gelu", backend=backend)
    return x + y


def _fused_layer_call(ph: Phase, bp: Any, xw: jax.Array, obs,
                      quantized: bool, backend: Optional[str],
                      bias: Optional[jax.Array],
                      mask: Optional[jax.Array],
                      shard: Optional[ShardCtx] = None) -> jax.Array:
    """One fused encoder layer over (B', N, C) — B' is images, or
    images * windows in W-MSA mode (the fold happens in `_layer_phase`)."""
    msa_axis = shard.reduce_axis(ph.path, "w_msa") if shard else None
    mlp_axis = shard.reduce_axis(ph.path, "w_down") if shard else None
    if quantized:
        # Frozen per-site activation scales feed the kernel's in-grid
        # requant chain — the same four sites the unfused executor
        # quantizes at, recorded by the (always unfused) calibration pass.
        act_scales = jnp.stack([
            obs.observe(f"{ph.site}.qkv_in", xw),
            obs.observe(f"{ph.site}.w_msa", xw),
            obs.observe(f"{ph.site}.w_up", xw),
            obs.observe(f"{ph.site}.w_down", xw)]).reshape(4)
        return ops.vita_layer_int8(
            xw, bp["wq"].values, bp["wk"].values, bp["wv"].values,
            bp["w_msa"].values, bp["w_up"].values, bp["w_down"].values,
            act_scales, _head_scale(bp["wq"]), _head_scale(bp["wk"]),
            _head_scale(bp["wv"]), bp["w_msa"].scale, bp["w_up"].scale,
            bp["w_down"].scale, bp["ln1_w"], bp["ln1_b"], bp["ln2_w"],
            bp["ln2_b"], bp["b_up"], bp["b_down"], bias, mask,
            backend=backend, msa_axis=msa_axis,
            mlp_axis=mlp_axis).astype(xw.dtype)
    return ops.vita_layer_fused(
        xw, bp["wq"], bp["wk"], bp["wv"], bp["w_msa"], bp["ln1_w"],
        bp["ln1_b"], bp["ln2_w"], bp["ln2_b"], bp["w_up"], bp["b_up"],
        bp["w_down"], bp["b_down"], bias, mask, backend=backend,
        msa_axis=msa_axis, mlp_axis=mlp_axis)


def _layer_phase(ph: Phase, bp: Any, x: jax.Array, obs, quantized: bool,
                 backend: Optional[str],
                 shard: Optional[ShardCtx] = None) -> jax.Array:
    """Fused encoder layer: msa -> concat -> mlp as one kernel chain.

    int8 calibration (observer not yet frozen) falls back to the unfused
    executors so the observer sees every intermediate activation at the
    same site names the fused kernel later consumes frozen scales for.
    """
    if quantized and (obs is None or obs.frozen is None):
        x = _msa_phase(ph, bp, x, obs, quantized, backend, shard)
        return _mlp_phase(ph, bp, x, obs, quantized, backend, shard)
    if not ph.window:
        return _fused_layer_call(ph, bp, x, obs, quantized, backend,
                                 None, None, shard)
    # W-MSA: LN / concat / residual / MLP are all per-token maps, so the
    # WHOLE fused layer commutes with the window permutation — fold the
    # windows into the batch axis, run the fused chain, unfold.
    xw, bias, mask = _window_fold(ph, x, bp["rel_bias"])
    yw = _fused_layer_call(ph, bp, xw, obs, quantized, backend, bias, mask,
                           shard)
    return _window_unfold(ph, yw, x.shape[0])


def _stack_block_params(bps) -> Dict[str, Any]:
    """Stack per-layer block subtrees into leading-axis (L, ...) operands
    for the layer-group megakernel.  `QTensor` leaves stack values and
    per-channel weight scales separately (`quant.stack_qtensors`), so the
    frozen scales ride the stacked pytree at per-layer granularity."""
    out: Dict[str, Any] = {}
    for k in bps[0]:
        vals = [bp[k] for bp in bps]
        out[k] = (stack_qtensors(vals) if isinstance(vals[0], QTensor)
                  else jnp.stack(vals))
    return out


def _group_head_scale(wq: QTensor) -> jax.Array:
    """Stacked per-(layer, head, out-channel) scale (L, H, 1, Dh) -> the
    (L, H, Dh) grouped-kernel form."""
    l, h, _, dh = wq.values.shape
    return wq.scale.reshape(l, h, dh)


def _grouped_layer_call(ph: Phase, sp: Dict[str, Any], xw: jax.Array, obs,
                        quantized: bool, backend: Optional[str],
                        bias: Optional[jax.Array],
                        mask: Optional[jax.Array],
                        shard: Optional[ShardCtx] = None) -> jax.Array:
    """One layer-group megakernel call over (B', N, C): ``sp`` holds the
    group's stacked (L, ...) weight operands; B' is images, or
    images * windows in W-MSA mode (the fold happens in the caller).
    Members share one sharding decision (identical shapes, hence
    identical specs), so the lead member's spec speaks for the group."""
    lead = ph.members[0]
    msa_axis = shard.reduce_axis(lead.path, "w_msa") if shard else None
    mlp_axis = shard.reduce_axis(lead.path, "w_down") if shard else None
    if quantized:
        # (L, 4) frozen activation scales: each member's four calibration
        # sites, recorded by the (always unfused) calibration pass.
        act_scales = jnp.stack([
            jnp.stack([obs.observe(f"{m.site}.qkv_in", xw),
                       obs.observe(f"{m.site}.w_msa", xw),
                       obs.observe(f"{m.site}.w_up", xw),
                       obs.observe(f"{m.site}.w_down", xw)]).reshape(4)
            for m in ph.members])
        return ops.vita_layer_group_int8(
            xw, sp["wq"].values, sp["wk"].values, sp["wv"].values,
            sp["w_msa"].values, sp["w_up"].values, sp["w_down"].values,
            act_scales, _group_head_scale(sp["wq"]),
            _group_head_scale(sp["wk"]), _group_head_scale(sp["wv"]),
            sp["w_msa"].scale, sp["w_up"].scale, sp["w_down"].scale,
            sp["ln1_w"], sp["ln1_b"], sp["ln2_w"], sp["ln2_b"],
            sp["b_up"], sp["b_down"], bias, mask,
            backend=backend, msa_axis=msa_axis,
            mlp_axis=mlp_axis).astype(xw.dtype)
    return ops.vita_layer_group(
        xw, sp["wq"], sp["wk"], sp["wv"], sp["w_msa"], sp["ln1_w"],
        sp["ln1_b"], sp["ln2_w"], sp["ln2_b"], sp["w_up"], sp["b_up"],
        sp["w_down"], sp["b_down"], bias, mask, backend=backend,
        msa_axis=msa_axis, mlp_axis=mlp_axis)


def _layer_group_phase(ph: Phase, params: Any, x: jax.Array, obs,
                       quantized: bool, backend: Optional[str],
                       shard: Optional[ShardCtx] = None) -> jax.Array:
    """Layer-group megakernel phase: L encoder blocks, one kernel chain.

    int8 calibration (observer not yet frozen) falls back to per-member
    `_layer_phase` calls (which themselves fall back unfused) so the
    observer sees every member's activation sites.  The window fold
    happens ONCE for the whole group — members share window/shift by the
    grouping pass's compatibility rule — so grouping commutes with the
    fold exactly as per-layer fusion does.
    """
    if quantized and (obs is None or obs.frozen is None):
        for m in ph.members:
            x = _layer_phase(m, _subtree(params, m.path), x, obs,
                             quantized, backend, shard)
        return x
    sp = _stack_block_params([_subtree(params, m.path)
                              for m in ph.members])
    if not ph.window:
        return _grouped_layer_call(ph, sp, x, obs, quantized, backend,
                                   None, None, shard)
    xw, bias, mask = _window_fold(ph, x, sp["rel_bias"])
    yw = _grouped_layer_call(ph, sp, xw, obs, quantized, backend,
                             bias, mask, shard)
    return _window_unfold(ph, yw, x.shape[0])


def _fold_phase(ph: Phase, bp: Any, x: jax.Array, inner: jax.Array,
                obs) -> jax.Array:
    """TNT re-entry: LN over each patch's flattened pixel tokens -> linear
    projection to the outer dim -> residual into the outer stream."""
    b, t, _ = x.shape
    flat = inner.reshape(b, t, -1)                  # (B, N, m*c)
    flat = ops.layer_norm(flat, bp["fold_ln_w"], bp["fold_ln_b"])
    return x + _matmul(flat, bp["fold_w"], obs, ph.site) + bp["fold_b"]


def _merge_phase(ph: Phase, sp: Any, x: jax.Array, obs) -> jax.Array:
    """Swin patch merging: 2x2 neighbourhood concat -> LN -> linear."""
    b, t, c = x.shape
    gh, gw = ph.grid
    xs = x.reshape(b, gh // 2, 2, gw // 2, 2, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, gh // 2, gw // 2, 4 * c)
    xs = ops.layer_norm(xs, sp["merge_ln_w"], sp["merge_ln_b"])
    xs = _matmul(xs, sp["merge_w"], obs, ph.site)
    return xs.reshape(b, (gh // 2) * (gw // 2), xs.shape[-1])


def _apply_phase(sched: Schedule, ph: Phase, params: Any,
                 x: Optional[jax.Array], inner: Optional[jax.Array],
                 obs, quantized: bool,
                 shard: Optional[ShardCtx] = None
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Execute ONE phase of the control program.

    The executor state is the (outer stream, inner stream) pair; every
    phase maps it to the next pair.  Shared by the whole-schedule replay
    (`run_schedule`) and the per-phase profiler (`profile_schedule`),
    which blocks and times each application separately.

    ``shard`` (shard_map mode): only the MSA/MLP/layer phases can hold
    model-axis-sharded weights; embed/fold/merge/head weights replicate,
    so those phases compute full-width results locally with no change.
    """

    def _float(v):
        return v.dequantize() if isinstance(v, QTensor) else v

    if ph.kind == "embed":
        if ph.inner_tokens:
            # TNT dual-stream frontend: sub-patches embed into the
            # inner stream; its flattened projection seeds the outer.
            b, t, _ = x.shape
            sub = pixel_partition(x, ph.inner_tokens)
            y = _matmul(sub, params["pixel_embed"], obs, "pixel_embed")
            inner = y + _float(params["inner_pos_embed"])[None]
            flat = ops.layer_norm(inner.reshape(b, t, -1),
                                  params["pe_ln_w"], params["pe_ln_b"])
            x = _matmul(flat, params["patch_embed"], obs, ph.site)
        else:
            x = _matmul(x, params["patch_embed"], obs, ph.site)
            if ph.norm:
                x = ops.layer_norm(x, params["pe_ln_w"],
                                   params["pe_ln_b"])
        if ph.pos_embed:
            x = x + _float(params["pos_embed"])[None]
    elif ph.kind == "msa":
        x = _msa_phase(ph, _subtree(params, ph.path), x, obs,
                       quantized, sched.backend, shard)
    elif ph.kind == "mlp":
        x = _mlp_phase(ph, _subtree(params, ph.path), x, obs,
                       quantized, sched.backend, shard)
    elif ph.kind == "layer":
        x = _layer_phase(ph, _subtree(params, ph.path), x, obs,
                         quantized, sched.backend, shard)
    elif ph.kind == "inner_layer":
        # Fused inner block: the pixel stream through the same fused
        # kernel chain (batch axis = images x patches).
        inner = _layer_phase(ph, _subtree(params, ph.path), inner, obs,
                             quantized, sched.backend, shard)
    elif ph.kind == "layer_group":
        # Megakernel: members carry their own param paths, so the group
        # phase receives the WHOLE tree and stacks the member subtrees.
        x = _layer_group_phase(ph, params, x, obs, quantized,
                               sched.backend, shard)
    elif ph.kind == "inner_layer_group":
        inner = _layer_group_phase(ph, params, inner, obs, quantized,
                                   sched.backend, shard)
    elif ph.kind == "inner_msa":
        # The pixel stream's batch axis already carries images x
        # patches, so the SAME phase executors (and the same
        # `(batch, head)` grid kernels) run the inner blocks.
        inner = _msa_phase(ph, _subtree(params, ph.path), inner, obs,
                           quantized, sched.backend, shard)
    elif ph.kind == "inner_mlp":
        inner = _mlp_phase(ph, _subtree(params, ph.path), inner, obs,
                           quantized, sched.backend, shard)
    elif ph.kind == "fold":
        x = _fold_phase(ph, _subtree(params, ph.path), x, inner, obs)
    elif ph.kind == "merge":
        x = _merge_phase(ph, _subtree(params, ph.path), x, obs)
    elif ph.kind == "head":
        x = ops.layer_norm(x, params["ln_f_w"], params["ln_f_b"])
        x = _matmul(jnp.mean(x, axis=1), params["head"], obs, ph.site)
    else:
        raise ValueError(f"unknown phase kind {ph.kind!r}")
    return x, inner


def run_schedule(sched: Schedule, params: Any, patches: jax.Array,
                 observer=None, *,
                 shard: Optional[ShardCtx] = None) -> jax.Array:
    """Replay a compiled schedule: patches (B, N, P*P*3) -> logits.

    Float params run through the Pallas/XLA batched ops; `QTensor` params
    plus a `core.quant.Calibrator` observer run the int8 PTQ path (the
    observer records activation amax when calibrating, returns frozen
    scales at inference).

    ``shard``: `ShardCtx` when the replay body runs under `shard_map`
    with model-axis-sharded params (see `build_sharded_fn`); None for
    single-device and GSPMD data-parallel execution.

    Each phase runs under its `phase_scopes` name, so the trace's device
    operations say which phase they belong to.
    """
    obs = observer
    quantized = isinstance(params["patch_embed"], QTensor)
    x = patches
    inner: Optional[jax.Array] = None      # TNT pixel stream (B*N, m, c)
    for ph, scope in zip(sched.phases, phase_scopes(sched)):
        with jax.named_scope(scope):
            x, inner = _apply_phase(sched, ph, params, x, inner, obs,
                                    quantized, shard)
    return x


def phase_scopes(sched: Schedule) -> Tuple[str, ...]:
    """The `jax.named_scope` each phase runs under in `run_schedule`:
    ``embed`` and ``head``, and every other phase its kind numbered in
    schedule order (``layer0`` ..., or ``msa0`` / ``mlp0`` ...,
    ``merge0``).  A device operation's metadata then names the phase it
    computes, whatever kernel implements it."""
    seen: Dict[str, int] = {}
    out = []
    for ph in sched.phases:
        if ph.kind in ("embed", "head"):
            out.append(ph.kind)
        else:
            k = seen.get(ph.kind, 0)
            seen[ph.kind] = k + 1
            out.append(f"{ph.kind}{k}")
    return tuple(out)


def profile_schedule(sched: Schedule, params: Any, patches: jax.Array,
                     observer=None, *, warmup: int = 1, repeats: int = 3
                     ) -> Tuple[jax.Array, list]:
    """Replay a schedule with per-phase timing: logits + one record per
    phase.

    Each phase is compiled as its OWN jitted program (the per-phase
    analogue of the unfused executor's kernel-launch boundaries) and
    timed with a block-until-ready barrier after every application —
    ``warmup`` full replays absorb compilation, then ``repeats`` timed
    replays run and each phase keeps its best (minimum) time, the
    standard noise-robust steady-state estimate.  Records are
    ``{"index", "kind", "site", "ms"}`` dicts in schedule order — feed
    them to `core.hue.live_hue_report` to join with the analytic
    `perfmodel.expected_phase_cycles` attribution.

    int8 profiling requires a *frozen* calibrator (calibration is a
    host-side amax loop that cannot run under jit); float params take
    ``observer=None`` as usual.
    """
    obs = observer
    assert obs is None or obs.frozen is not None, \
        "profiling needs frozen calibration scales (or float mode)"
    quantized = isinstance(params["patch_embed"], QTensor)

    def _phase_fn(ph: Phase):
        def fn(p, x, inner):
            return _apply_phase(sched, ph, p, x, inner, obs, quantized)
        return jax.jit(fn)

    fns = [_phase_fn(ph) for ph in sched.phases]
    best = [float("inf")] * len(sched.phases)
    for it in range(max(warmup, 0) + max(repeats, 1)):
        timed = it >= warmup
        x, inner = patches, None
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            x, inner = fn(params, x, inner)
            jax.block_until_ready(x)
            if inner is not None:
                jax.block_until_ready(inner)
            if timed:
                best[i] = min(best[i], time.perf_counter() - t0)
    records = [{"index": i, "kind": ph.kind, "site": ph.site,
                "ms": best[i] * 1e3}
               for i, ph in enumerate(sched.phases)]
    return x, records


# ---------------------------------------------------------------------------
# Fusion policy (cost-model- and measurement-driven fuse/don't-fuse)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class FusionPolicy:
    """Decides, per served (model, mode, batch), whether the fused
    ``layer``-phase schedule or the per-phase one runs.

    The analytic model (`perfmodel.fusion_speedup_model`) predicts fusion
    always wins on the ViTA datapath (1.23-1.40x), but the bench measures
    the CPU-interpreter backend *losing* on several configurations — a
    gap nothing used to act on.  Modes:

      * ``always`` — the pre-policy default: serve the fused schedule
        (grouped at ``default_group`` when a group size is configured);
      * ``never``  — the ``--no-fuse`` A/B twin: per-phase execution;
      * ``auto``   — consult measured A/B data (``measurements`` maps
        ``(model, mode, batch) -> fusion_speedup`` of the per-layer fused
        chain; ``group_measurements`` maps the same key to
        ``(fusion_speedup, group_size)`` of the layer-group chain — both
        seeded from a ``BENCH_vision_serve.json`` via `from_bench`): the
        policy picks whichever of {unfused, per-layer fused, grouped}
        measured fastest, fusing iff the winner's speedup is >=
        ``threshold``.  An exact-batch miss falls back to the nearest
        measured batch of the same (model, mode); a total miss falls back
        to ``default_fused`` (the model's prediction — fuse) at
        ``default_group``.
    """

    mode: str = "always"
    measurements: Dict[Tuple[str, str, int], float] = \
        dataclasses.field(default_factory=dict)
    group_measurements: Dict[Tuple[str, str, int], Tuple[float, int]] = \
        dataclasses.field(default_factory=dict)
    threshold: float = 1.0
    default_fused: bool = True
    default_group: int = 1

    MODES = ("always", "never", "auto")

    def __post_init__(self):
        assert self.mode in self.MODES, \
            f"fusion policy mode must be one of {self.MODES}, " \
            f"got {self.mode!r}"

    @classmethod
    def from_bench(cls, record: Any, mode: str = "auto",
                   **kw) -> "FusionPolicy":
        """Seed ``auto`` measurements from a bench record (a loaded
        ``BENCH_vision_serve.json`` dict, or a path to one).  Reads the
        measured ``fusion_speedup`` off fused rows (current schema) and
        tolerates the pre-observability files that duplicated it onto
        both rows of the A/B pair; sharded rows (no unfused twin,
        ``fusion_speedup`` null) are skipped."""
        if isinstance(record, (str, bytes)):
            import json
            with open(record) as f:
                record = json.load(f)
        meas: Dict[Tuple[str, str, int], float] = {}
        grp: Dict[Tuple[str, str, int], Tuple[float, int]] = {}
        for r in record.get("runs", []):
            fs = r.get("fusion_speedup")
            if not (r.get("fused") and isinstance(fs, (int, float))):
                continue
            key = (r["model"], r["mode"], int(r["batch"]))
            gs = int(r.get("group_size", 1))
            if gs > 1:
                grp[key] = (float(fs), gs)
            else:
                meas[key] = float(fs)
        return cls(mode=mode, measurements=meas, group_measurements=grp,
                   **kw)

    @staticmethod
    def _nearest(table, model: str, mode: str, batch: int):
        """Exact-key lookup, falling back to the nearest measured batch
        of the same (model, mode); None on a total miss."""
        key = (model, mode, int(batch))
        if key in table:
            return table[key]
        near = [(abs(b - batch), b) for (m, md, b) in table
                if m == model and md == mode]
        if near:
            return table[(model, mode, min(near)[1])]
        return None

    def decide(self, model: str, mode: str, batch: int) -> bool:
        """Fused (per-layer OR grouped) vs unfused for one configuration."""
        if self.mode == "always":
            return True
        if self.mode == "never":
            return False
        s1 = self._nearest(self.measurements, model, mode, batch)
        sg = self._nearest(self.group_measurements, model, mode, batch)
        cands = [s for s in (s1, sg[0] if sg else None) if s is not None]
        if not cands:
            return self.default_fused
        return max(cands) >= self.threshold

    def decide_group(self, model: str, mode: str, batch: int) -> int:
        """Group size of the fused variant `decide` picked (1 = the
        per-layer chain).  Only meaningful when `decide` returns True."""
        if self.mode == "never":
            return 1
        if self.mode == "always":
            return self.default_group
        sg = self._nearest(self.group_measurements, model, mode, batch)
        if sg is None:
            return self.default_group if \
                self._nearest(self.measurements, model, mode, batch) \
                is None else 1
        s1 = self._nearest(self.measurements, model, mode, batch)
        spd, gs = sg
        if spd >= self.threshold and (s1 is None or spd >= s1):
            return gs
        return 1

    def decisions(self, model: str, mode: str,
                  batches: Sequence[int]) -> Dict[int, bool]:
        return {int(b): self.decide(model, mode, b) for b in batches}

    def group_decisions(self, model: str, mode: str,
                        batches: Sequence[int]) -> Dict[int, int]:
        return {int(b): self.decide_group(model, mode, b) for b in batches}


# ---------------------------------------------------------------------------
# Mesh-aware executor entry (data-parallel batch grid, 2-D latency mesh)
# ---------------------------------------------------------------------------


def place_schedule_inputs(params: Any, patches: jax.Array, mesh):
    """Place executor inputs under `NamedSharding` for a serving mesh.

    Params (float arrays or int8 `QTensor`s — whose per-channel weight
    scales ride along as pytree children) replicate across the data axes;
    on a 2-D ``("data", "model")`` mesh the per-head stacks / MLP columns
    additionally shard over ``model`` (`vision_param_specs`).  The patch
    batch shards over ``data`` when the batch size divides the axis,
    falling back to replication otherwise (the `_fits` ladder — never a
    compile error).  The frozen activation-calibration scales are closure
    scalars inside the jitted replay and replicate on their own.
    """
    from repro.distributed import sharding as shd
    return (shd.shard_vision_params(params, mesh),
            shd.shard_vision_batch(patches, mesh))


def build_sharded_fn(sched: Schedule, params: Any, mesh, *, batch: int,
                     observer=None, preprocess=None, x_ndim: int = 3):
    """Build the `shard_map` executor body for a serving mesh.

    Returns an UNJITTED ``fn(params, x) -> logits`` closure: the schedule
    replay wrapped in `shard_map` over the full mesh, with in_specs read
    straight from `vision_param_specs` (weights arrive as local head /
    MLP-column shards) and a `ShardCtx` telling the executor where its
    two per-block all-reduces fire.  The batch rides ``data`` when
    ``batch`` divides it and replicates otherwise — the batch=1 latency
    case: every data row computes identical logits while the model axis
    still splits the head grid.

    Why not GSPMD: it cannot partition a Pallas (Mosaic) kernel at all,
    so on the TPU even a 1-D data mesh must hand each device its own
    rows; and on the model axis, the fused oracle's merged-QKV
    formulation (`kernels.ref._merge_qkv` — transpose+reshape+concat over
    the head-sharded dim) is miscompiled by the XLA SPMD partitioner
    (wrong VALUES, not an error), while the same program under
    `shard_map` sees only local shards and never partitions the reshape.
    On a mesh with no ``model`` axis every weight replicates and no psum
    fires.

    ``preprocess`` runs inside the shard_map body on the local batch rows
    before the replay (the server passes `vit.extract_patches` so images
    stream sharded, ``x_ndim=4``).  int8 requires a frozen calibrator:
    its scales are host scalars closed over the body, replicated for
    free.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shd

    specs = shd.vision_param_specs(params, mesh)
    shard = ShardCtx(axis="model", specs=specs)
    bspec = shd.vision_batch_spec(int(batch), mesh)
    bax = tuple(bspec)[0] if len(tuple(bspec)) else None

    def _full_rank(spec, leaf):
        dims = tuple(spec)
        return P(*(dims + (None,) * (leaf.ndim - len(dims))))

    pspecs = jax.tree_util.tree_map(
        _full_rank, specs, params, is_leaf=lambda s: isinstance(s, P))
    x_spec = P(*((bax,) + (None,) * (x_ndim - 1)))

    def body(p, x):
        if preprocess is not None:
            x = preprocess(x)
        return run_schedule(sched, p, x, observer=observer, shard=shard)

    return jax.shard_map(body, mesh=mesh, in_specs=(pspecs, x_spec),
                         out_specs=P(bax, None), check_vma=False)


def run_schedule_sharded(sched: Schedule, params: Any, patches: jax.Array,
                         mesh, observer=None) -> jax.Array:
    """`run_schedule`, distributed over a device mesh through
    `build_sharded_fn`.

    On a 1-D ``("data",)`` mesh each device replays the schedule on its
    batch rows: every phase — including the fused ``layer`` /
    ``inner_layer`` kernel chains and the window/pixel folds, which only
    reshape *within* an image's batch row — is independent per image.
    On a 2-D ``("data", "model")`` mesh the head grid and MLP columns
    additionally split over ``model``, with explicit psums at the two
    residual re-entries.  int8 requires a *frozen* calibrator either way
    (calibration itself is a host-side amax loop and stays
    single-device).

    Serving keeps its own per-bucket jit cache (`VisionServer`); this
    entry compiles per call and is meant for tests and one-shot runs.
    """
    assert observer is None or observer.frozen is not None, \
        "sharded execution needs frozen calibration scales (or float mode)"
    params, patches = place_schedule_inputs(params, patches, mesh)
    fn = build_sharded_fn(sched, params, mesh, batch=patches.shape[0],
                          observer=observer)
    return jax.jit(fn)(params, patches)
