"""GSPMD sharding rules for every architecture / shape cell.

Baseline parallelism (single pod 16x16, multi-pod 2x16x16):
  * ``data`` (+ ``pod``)  — batch data-parallel; gradient reduction crosses
    pods once per step (DCN-friendly).
  * ``model``             — 16-way tensor parallel: column-parallel up/QKV
    projections, row-parallel down/output projections (Megatron scheme),
    vocab-sharded embeddings (padded to /256 so every table divides),
    expert-parallel MoE when n_experts divides the axis (olmoe), otherwise
    TP inside experts (mixtral).

Rules are *name-based with divisibility fallbacks*: a preferred spec whose
dimension does not divide the mesh axis degrades to replication on that
dimension (never a compile error).  This is what lets one rule set cover
head_dim=80 (stablelm), kv_heads=1 (recurrentgemma MQA), 8 experts on a
16-way axis (mixtral), etc.

Stacked layer params (leading n_superblocks dim from the scan) get a
prepended None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


# name -> (spec for the *unstacked* shape); "M" = model axis placeholder
_COL = ("wq", "wk", "wv", "w_up", "w_gate", "w_x", "w_gate_branch",
        "w_in", "w_z", "w_q", "w_k", "w_v", "w_input_gate", "w_rec_gate",
        "unembed", "in_proj")
_ROW = ("wo", "w_down", "w_out", "w_msa")
_COL_BIAS = ("bq", "bk", "bv", "b_up", "b_in", "a_param", "gn_w")


def axis_size(mesh, name: str) -> int:
    """Size of a mesh axis by name (1 if absent); works on `Mesh` and
    `AbstractMesh` across API generations."""
    sizes = getattr(mesh, "axis_sizes", None)
    if sizes is None:
        sizes = mesh.devices.shape
    return dict(zip(mesh.axis_names, sizes)).get(name, 1)


_axis_size = axis_size          # internal call sites / back-compat


def _fits(shape: Tuple[int, ...], spec: Sequence, mesh: Mesh) -> P:
    """Replace axis names that don't divide the dim with None."""
    fixed = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        size = np.prod([_axis_size(mesh, a) for a in
                        (ax if isinstance(ax, tuple) else (ax,))])
        fixed.append(ax if dim % int(size) == 0 else None)
    return P(*fixed)


def _param_rule(path_keys: Tuple[str, ...], shape: Tuple[int, ...],
                mesh: Mesh, cfg: ModelConfig) -> P:
    name = path_keys[-1]
    stacked = "layers" in path_keys
    base_shape = shape[1:] if stacked else shape

    in_moe = "moe" in path_keys
    if in_moe and name in ("w_up", "w_gate", "w_down"):
        e = base_shape[0]
        if e % _axis_size(mesh, "model") == 0:
            spec = ("model", None, None)                  # expert parallel
        elif name == "w_down":
            spec = (None, "model", None)                  # TP inside expert
        else:
            spec = (None, None, "model")
    elif in_moe and name == "router":
        spec = (None, None)
    elif name == "embed":
        spec = ("model", None)
    elif name in _COL and len(base_shape) == 2:
        spec = (None, "model")
    elif name in ("w_q", "w_k", "w_v") and len(base_shape) == 3:
        spec = (None, None, "model")        # block-diagonal per-head (xLSTM)
    elif name in _ROW and len(base_shape) == 2:
        spec = ("model", None)
    elif name == "conv_w":
        spec = (None, "model")
    elif name in _COL_BIAS and len(base_shape) == 1:
        spec = ("model",)
    else:
        spec = (None,) * len(base_shape)
    if stacked:
        spec = (None,) + tuple(spec)
        base_shape = shape
    return _fits(shape, spec, mesh)


def param_specs(cfg: ModelConfig, params_shape: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree matching a params (shape) tree."""
    def rule(path, leaf):
        keys = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)
        return _param_rule(keys, tuple(leaf.shape), mesh, cfg)
    return jax.tree_util.tree_map_with_path(rule, params_shape)


def named(tree: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _batch_axis(batch_size: int, mesh: Mesh):
    """Largest prefix of (pod, data) that divides the batch."""
    axes = dp_axes(mesh)
    size = int(np.prod([_axis_size(mesh, a) for a in axes]))
    if axes and batch_size % size == 0:
        return axes if len(axes) > 1 else axes[0]
    if "data" in mesh.axis_names and batch_size % _axis_size(
            mesh, "data") == 0:
        return "data"
    return None


def train_batch_specs(cfg: ModelConfig, batch_shapes: Dict[str, Any],
                      mesh: Mesh) -> Dict[str, P]:
    specs = {}
    for k, v in batch_shapes.items():
        b = v.shape[0]
        ax = _batch_axis(b, mesh)
        specs[k] = P(ax, *([None] * (len(v.shape) - 1)))
    return specs


def cache_spec_tree(cfg: ModelConfig, caches_shape: Any, mesh: Mesh,
                    batch_size: int) -> Any:
    """Specs for the stacked cache pytree (leading dim = n_superblocks)."""
    bax = _batch_axis(batch_size, mesh)
    m = _axis_size(mesh, "model")

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        name = str(getattr(path[-1], "key", path[-1]))
        # all caches: (sb, B, ...)
        spec = [None, bax]
        rest = shape[2:]
        if name in ("k", "v") and len(rest) == 3:       # (Hkv, S, Dh)
            hkv, s, dh = rest
            if hkv % m == 0:
                spec += ["model", None, None]
            elif dh % m == 0:
                spec += [None, None, "model"]
            else:
                spec += [None, None, None]
        elif name in ("h", "c", "n", "m", "conv", "C"):
            # recurrent states: shard the (last) feature dim when divisible
            sub = [None] * len(rest)
            for i in range(len(rest) - 1, -1, -1):
                if rest[i] % m == 0:
                    sub[i] = "model"
                    break
            spec += sub
        else:
            spec += [None] * len(rest)
        return _fits(shape, tuple(spec), mesh)

    return jax.tree_util.tree_map_with_path(rule, caches_shape)


# ---------------------------------------------------------------------------
# Vision serving specs (data-parallel batch grid + model-axis head grid)
# ---------------------------------------------------------------------------
#
# The vision pipeline's unit of work is the `(batch, head)` kernel grid with
# the batch axis outermost-parallel (core/schedule.py), so the serving shard
# rule is: batch on ``data``, params replicated over ``data``.  When the mesh
# carries a ``model`` axis the head grid additionally splits across it
# (heads are independent until the concat projection — the ViTA head-level
# pipeline's own parallel axis):
#
#   * ``wq/wk/wv`` (H, D, Dh) stacks — and their (H, 1, Dh) per-head int8
#     scales — shard the head dim when H divides the axis (`_fits` ladder);
#   * ``rel_bias`` ((2w-1)^2, H) Swin bias tables shard their head dim with
#     the block's stacks (same H, same ladder);
#   * ``w_msa`` (C, C) concat projections row-shard (Megatron row-parallel:
#     each device holds the rows matching ITS heads, the executor psums the
#     partial products at the residual) — but ONLY when the block's heads
#     sharded, so local shapes always line up under `shard_map`;
#   * ``w_up`` (C, hid) column-shards with ``b_up`` (hid,), and ``w_down``
#     (hid, C) row-shards, when the MLP hidden dim divides — the classic
#     column-then-row pair with one all-reduce at the residual re-entry.
#     int8 per-out-channel scales follow their values ((1, hid) shards its
#     channel dim with w_up; (1, C) contraction-side scales replicate via
#     the same `_fits` fallback).
#
# The same nested subtree layout covers all four families (ViT/DeiT flat
# ``layers``, Swin ``stages/blocks``, TNT ``inner``/``outer``).  Divisibility
# never errors: a dim that doesn't divide degrades to replication.


_VISION_PER_HEAD = ("wq", "wk", "wv")


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _vision_head_map(params: Any) -> Dict[Tuple[str, ...], Tuple[int, int]]:
    """(block path-name prefix) -> (H, Dh), read off each block's ``wq``
    stack.  Keys every per-block coherence decision (may ``w_msa`` row-shard?)
    off the SAME head count its wq/wk/wv ladder used."""
    heads: Dict[Tuple[str, ...], Tuple[int, int]] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        names = _path_names(path)
        if "wq" in names and len(leaf.shape) == 3:
            heads[names[:names.index("wq")]] = \
                (leaf.shape[0], leaf.shape[2])
    return heads


def vision_param_specs(params: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree for a vision param tree (float or int8 PTQ).

    Everything replicates over the data-parallel axes; on a mesh with a
    ``model`` axis the per-head QKV stacks (+ Swin bias tables) shard
    head-wise, the concat projection row-shards with its block's heads, and
    the MLP up/down pair column/row-shards — each through the `_fits`
    divisibility ladder (replication fallback, never a compile error).
    The executor (`core.schedule.ShardCtx`) reads THIS tree back to decide
    where its `shard_map` all-reduces fire, so rule and collective can
    never disagree.
    """
    has_model = "model" in mesh.axis_names
    m = _axis_size(mesh, "model")
    heads = _vision_head_map(params) if has_model else {}

    def rule(path, leaf):
        shape = tuple(leaf.shape)
        names = _path_names(path)
        if not has_model:
            return P()
        if len(shape) == 3 and any(n in _VISION_PER_HEAD for n in names):
            # (H, D, Dh) weight stack — or its (H, 1, Dh) per-head scale
            return _fits(shape, ("model", None, None), mesh)
        if "rel_bias" in names and len(shape) == 2:
            # ((2w-1)^2, H) bias table: heads ride dim 1, same ladder (and
            # the same H) as the block's wq stack, so bias rows always
            # land on the device holding their heads
            return _fits(shape, (None, "model"), mesh)
        if "w_msa" in names and len(shape) == 2:
            # (C, C) concat projection: row-shard iff this block's heads
            # sharded AND the concat dim is exactly H*Dh (head-major), so
            # each row block matches the local heads' concat slice; the
            # (1, C) int8 scale fails the H*Dh check and replicates
            hd = heads.get(names[:names.index("w_msa")])
            if hd and hd[0] % m == 0 and shape[0] == hd[0] * hd[1]:
                return _fits(shape, ("model", None), mesh)
            return P()
        if "w_up" in names and len(shape) == 2:
            # (C, hid) values and (1, hid) scale: column-parallel
            return _fits(shape, (None, "model"), mesh)
        if "b_up" in names and len(shape) == 1:
            return _fits(shape, ("model",), mesh)
        if "w_down" in names and len(shape) == 2:
            # (hid, C) values row-parallel; the (1, C) scale's dim 0 is 1
            # so `_fits` replicates it (it scales the FULL-width partial)
            return _fits(shape, ("model", None), mesh)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params)


def vision_batch_spec(batch_size: int, mesh: Mesh) -> P:
    """Batch-axis spec for the serving micro-batch: the largest (pod, data)
    prefix that divides the batch, else replication (never a compile
    error) — the same fallback ladder as `_batch_axis`."""
    return P(_batch_axis(batch_size, mesh))


def shard_vision_params(params: Any, mesh: Mesh) -> Any:
    """`device_put` a vision param tree under its NamedSharding tree."""
    return jax.device_put(params, named(vision_param_specs(params, mesh),
                                        mesh))


def shard_vision_batch(batch: Any, mesh: Mesh) -> Any:
    """`device_put` a (B, ...) activation batch, sharded over ``data`` when
    B divides, replicated otherwise."""
    spec = vision_batch_spec(batch.shape[0], mesh)
    return jax.device_put(batch, NamedSharding(mesh, spec))


def fsdp_widen(param_spec_tree: Any, params_shape: Any, mesh,
               min_elems: int = 1 << 20) -> Any:
    """ZeRO-3/FSDP: additionally shard big params over ``data`` at rest.
    XLA inserts the per-layer all-gathers; grads reduce-scatter back."""
    dsize = _axis_size(mesh, "data")

    def widen(spec, leaf):
        n = 1
        for s in leaf.shape:
            n *= s
        if n < min_elems or dsize <= 1:
            return spec
        dims = list(tuple(spec)) + \
            [None] * (len(leaf.shape) - len(tuple(spec)))
        for i, (dim, ax) in enumerate(zip(leaf.shape, dims)):
            if ax is None and dim % dsize == 0:
                dims[i] = "data"
                break
        return P(*dims)

    flat_s, treedef = jax.tree_util.tree_flatten(
        param_spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_l = treedef.flatten_up_to(params_shape)
    return treedef.unflatten([widen(s, l) for s, l in zip(flat_s, flat_l)])


def opt_state_specs(param_spec_tree: Any, params_shape: Any = None,
                    mesh=None, zero1: bool = True) -> Any:
    """Optimizer-moment sharding.

    Default = ZeRO-1: moments additionally shard their first
    data-divisible unsharded dim over ``data`` (Adam state for a 46B model
    never fits at DP x TP16 alone — verified by tests/test_sharding.py).
    """
    mom = param_spec_tree
    if zero1 and params_shape is not None and mesh is not None:
        dsize = _axis_size(mesh, "data")

        def widen(spec, leaf):
            dims = list(tuple(spec)) + \
                [None] * (len(leaf.shape) - len(tuple(spec)))
            for i, (dim, ax) in enumerate(zip(leaf.shape, dims)):
                if ax is None and dim % dsize == 0 and dsize > 1:
                    dims[i] = "data"
                    break
            return P(*dims)

        flat_s, treedef = jax.tree_util.tree_flatten(
            param_spec_tree, is_leaf=lambda x: isinstance(x, P))
        flat_l = treedef.flatten_up_to(params_shape)
        mom = treedef.unflatten([widen(s, l)
                                 for s, l in zip(flat_s, flat_l)])
    return {
        "m": mom,
        "v": mom,
        "count": P(),
    }
