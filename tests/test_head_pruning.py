"""Head-mask pruning: grid sizing, parameter slicing, ragged grouping,
and the zeroed-head dense oracle.

The pruning contract is parameter-level: `prune_block_heads` slices the
per-head wq/wk/wv stacks (QTensor scales follow their values), the Swin
rel_bias head columns, and the w_msa concat rows with the H/K rescale
folded in — the kernels derive their head extent from operand shapes and
never see dead heads.  `expand_block_heads` is the inverse oracle: the
DENSE schedule over zero-padded params must reproduce the pruned
execution.  A zero head computes exact zeros and the concat adds exact
0.0 terms / int8 zero rows, so int8 matches bit for bit.  Float matches
to a few f32 ulps (`FLOAT_ORACLE_ATOL`): the concat projection contracts
over K·Dh rows pruned and H·Dh rows dense, and XLA may sum the two
contractions in a different order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import schedule as sched_lib
from repro.core.perfmodel import head_segments
from repro.core.quant import (Calibrator, QTensor, expand_block_heads,
                              quantize, quantize_per_channel,
                              slice_concat_rows, slice_head_stack)
from repro.launch.vision_serve import build_edge_vit
from repro.models import swin, tnt, vision_registry, vit

from _hypothesis_compat import given, settings, strategies as st

# Tiny ViT geometry shared by the property tests: 3 layers x 4 heads ->
# a 12-bit integer encodes one full per-layer mask (bit li*4+h = head h
# of layer li alive); rows decoded all-dead keep one head, so every
# drawn integer is a valid ragged mask.
LAYERS, HEADS = 3, 4
MASK_BITS = st.integers(min_value=0, max_value=2 ** (LAYERS * HEADS) - 1)

# Float pruned-vs-dense-oracle bound on logits of magnitude ~1: about 16
# f32 ulps of 1.0 (the registry variants measure up to 7e-7).
FLOAT_ORACLE_ATOL = 2e-6


def _mask_from_bits(bits):
    rows = []
    for li in range(LAYERS):
        row = [(bits >> (li * HEADS + h)) & 1 for h in range(HEADS)]
        if not any(row):
            row[li % HEADS] = 1
        rows.append(tuple(row))
    return tuple(rows)


def _tiny_cfg(mask, *, fused=False, **kw):
    cfg = build_edge_vit(image=16, patch=8, dim=32, heads=HEADS,
                         layers=LAYERS, n_classes=8, **kw)
    return dataclasses.replace(cfg, head_mask=mask, fused=fused)


def _msa_heads(sched):
    return [p.heads for p in sched.phases if p.kind == "msa"]


def _layer_heads_in_order(sched):
    """Per-layer surviving heads read off a fused schedule, expanding
    layer_group members in execution order."""
    out = []
    for p in sched.phases:
        if p.kind == "layer_group":
            out.extend(m.heads for m in p.members)
        elif p.kind == "layer":
            out.append(p.heads)
    return out


# ---------------------------------------------------------------------------
# Property: masked grids have exactly the surviving-head extent
# ---------------------------------------------------------------------------


@given(MASK_BITS)
@settings(max_examples=25, deadline=None)
def test_masked_grid_extent_matches_mask(bits):
    """Schedule phases and sliced params both size their head axis to the
    mask's row sums — never the architectural count."""
    mask = _mask_from_bits(bits)
    counts = [sum(row) for row in mask]
    cfg = _tiny_cfg(mask)

    assert _msa_heads(vit.schedule(cfg)) == counts

    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    dh = cfg.dim // cfg.heads
    for lp, k in zip(params["layers"], counts):
        assert lp["wq"].shape == (k, cfg.dim, dh)
        assert lp["wk"].shape == (k, cfg.dim, dh)
        assert lp["wv"].shape == (k, cfg.dim, dh)
        assert lp["w_msa"].shape == (k * dh, cfg.dim)


@given(MASK_BITS)
@settings(max_examples=25, deadline=None)
def test_ragged_grouping_is_exact_cover(bits):
    """Fused+grouped schedules split layer groups exactly at head-count
    boundaries: groups are head-uniform, no layer is dropped or
    duplicated, and the segment decomposition matches `head_segments`."""
    mask = _mask_from_bits(bits)
    counts = [sum(row) for row in mask]
    cfg = _tiny_cfg(mask, fused=True)
    grouped = vit.schedule(dataclasses.replace(cfg, fuse_group=LAYERS))

    # exact cover, in layer order
    assert _layer_heads_in_order(grouped) == counts
    for p in grouped.phases:
        if p.kind == "layer_group":
            assert len({m.heads for m in p.members}) == 1
            assert p.heads == p.members[0].heads

    # the run-length decomposition the grouping pass respects
    segs = head_segments(counts)
    assert sum(segs) == len(counts)
    assert all(s >= 1 for s in segs)
    # reconstruct: each segment is a maximal constant run
    pos, run_counts = 0, []
    for s in segs:
        run = counts[pos:pos + s]
        assert len(set(run)) == 1
        run_counts.append(run[0])
        pos += s
    assert all(a != b for a, b in zip(run_counts, run_counts[1:]))
    # no layer_group spans more layers than its segment allows
    group_lens = [len(p.members) for p in grouped.phases
                  if p.kind == "layer_group"]
    assert all(g <= max(segs) for g in group_lens)


# ---------------------------------------------------------------------------
# Property: int8 scale slicing follows the values
# ---------------------------------------------------------------------------


@given(MASK_BITS, st.integers(min_value=0, max_value=10 ** 6))
@settings(max_examples=25, deadline=None)
def test_qtensor_slicing_scales_follow_values(bits, seed):
    """`slice_head_stack` keeps (values, scale) row pairs together;
    `slice_concat_rows` slices int8 rows untouched and folds the H/K
    concat rescale into the per-out-channel scale (float: into values)."""
    row = _mask_from_bits(bits)[0]
    keep = [i for i, v in enumerate(row) if v]
    k, dh, d = len(keep), 3, 8
    rng = np.random.default_rng(seed)

    stack = jnp.asarray(rng.standard_normal((HEADS, d, dh)),
                        dtype=jnp.float32)
    qstack = quantize(stack, jnp.abs(stack).max(axis=(1, 2),
                                               keepdims=True) / 127.0)
    sliced = slice_head_stack(qstack, keep)
    assert sliced.values.shape == (k, d, dh)
    assert jnp.array_equal(sliced.values, qstack.values[np.asarray(keep)])
    assert jnp.array_equal(sliced.scale, qstack.scale[np.asarray(keep)])

    w = jnp.asarray(rng.standard_normal((HEADS * dh, d)),
                    dtype=jnp.float32)
    rescale = HEADS / float(k)
    fs = slice_concat_rows(w, keep, HEADS)
    want_rows = w.reshape(HEADS, dh, d)[np.asarray(keep)].reshape(k * dh, d)
    assert jnp.array_equal(fs, want_rows * rescale)

    qw = quantize_per_channel(w)
    qs = slice_concat_rows(qw, keep, HEADS)
    qrows = qw.values.reshape(HEADS, dh, d)[np.asarray(keep)]
    assert jnp.array_equal(qs.values, qrows.reshape(k * dh, d))
    assert jnp.array_equal(qs.scale, qw.scale * rescale)


# ---------------------------------------------------------------------------
# Property: masked parity vs the zeroed-head dense oracle (tiny ViT)
# ---------------------------------------------------------------------------


@given(MASK_BITS)
@settings(max_examples=6, deadline=None)
def test_masked_parity_vs_zeroed_dense_oracle(bits):
    """Pruned execution == dense schedule over zero-expanded params,
    bit-for-bit (exact zeros through matmul + concat accumulation)."""
    mask = _mask_from_bits(bits)
    cfg = _tiny_cfg(mask)
    dense_cfg = dataclasses.replace(cfg, head_mask=None)
    params = vit.init_params(jax.random.PRNGKey(1), cfg)
    expanded = dict(params)
    expanded["layers"] = [expand_block_heads(bp, row)
                          for bp, row in zip(params["layers"], mask)]
    imgs = np.random.default_rng(2).standard_normal(
        (2, cfg.image, cfg.image, 3)).astype(np.float32)
    patches = vit.extract_patches(jnp.asarray(imgs), cfg.patch)
    pruned = vit.forward(params, patches, cfg)
    oracle = vit.forward(expanded, patches, dense_cfg)
    assert jnp.array_equal(pruned, oracle), (
        np.abs(np.asarray(pruned) - np.asarray(oracle)).max())


# ---------------------------------------------------------------------------
# Registry pruned variants: bit-exact float + int8 oracle parity
# ---------------------------------------------------------------------------


def _expand_params(cfg, params):
    """Zero-expand a pruned param tree to the dense twin's geometry."""
    out = dict(params)
    if isinstance(cfg, swin.SwinConfig):
        stages = []
        for s_i, sp in enumerate(params["stages"]):
            sp = dict(sp)
            sp["blocks"] = [expand_block_heads(bp, row) for bp, row
                            in zip(sp["blocks"], cfg.stage_mask(s_i))]
            stages.append(sp)
        out["stages"] = stages
    elif isinstance(cfg, tnt.TNTConfig):
        layers = []
        for lp, row in zip(params["layers"], cfg.head_mask):
            lp = dict(lp)
            lp["outer"] = expand_block_heads(lp["outer"], row)
            layers.append(lp)
        out["layers"] = layers
    else:
        out["layers"] = [expand_block_heads(bp, row) for bp, row
                         in zip(params["layers"], cfg.head_mask)]
    return out


PRUNED = [m for m in vision_registry.list_models() if m.endswith("_p")]


@pytest.mark.parametrize("name", PRUNED)
@pytest.mark.parametrize("mode", ["float", "int8"])
def test_pruned_variant_matches_dense_oracle(name, mode):
    """Each registered pruned variant reproduces the dense schedule over
    its zero-expanded params — int8 exactly, float to a few ulps — the
    acceptance oracle for the ragged masks shipping in the registry."""
    cfg = vision_registry.build_cfg(name)
    assert cfg.head_mask is not None
    dense_cfg = dataclasses.replace(cfg, head_mask=None)
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    imgs = np.random.default_rng(3).standard_normal(
        (2, cfg.image, cfg.image, 3)).astype(np.float32)
    patches = vit.extract_patches(jnp.asarray(imgs), cfg.patch)
    fwd = vision_registry.forward_fn(cfg)

    if mode == "float":
        pruned = fwd(params, patches, cfg)
        oracle = fwd(_expand_params(cfg, params), patches, dense_cfg)
    else:
        qparams = vision_registry.quantize(params)
        cal = Calibrator()
        fwd(qparams, patches, cfg, observer=cal)
        cal.freeze()
        pruned = fwd(qparams, patches, cfg, observer=cal)
        # same frozen scales drive the oracle: activations are identical,
        # so the requant chain quantizes to the same integers
        oracle = fwd(_expand_params(cfg, qparams), patches, dense_cfg,
                     observer=cal)
    if mode == "float":
        np.testing.assert_allclose(pruned, oracle, rtol=0,
                                   atol=FLOAT_ORACLE_ATOL, err_msg=name)
    else:
        assert jnp.array_equal(pruned, oracle), (
            name, mode,
            np.abs(np.asarray(pruned) - np.asarray(oracle)).max())


@pytest.mark.parametrize("name", PRUNED)
def test_pruned_variant_schedule_is_ragged(name):
    """The shipped masks are genuinely ragged (at least two distinct
    surviving-head counts) and the schedule reflects them per layer."""
    cfg = vision_registry.build_cfg(name)
    spec = vision_registry.make_spec(cfg)
    counts = [h for stg in spec.stages for h in stg.head_counts]
    assert len(set(counts)) >= 2, counts
    sched = vision_registry.make_schedule(
        dataclasses.replace(cfg, fused=False))
    assert _msa_heads(sched) == counts


def test_expand_block_heads_roundtrip_shapes():
    """expand(prune(x)) restores dense shapes with zeros exactly at the
    dead positions (spot-check of the oracle's padding layout)."""
    cfg = _tiny_cfg(None)
    dense = vit.init_params(jax.random.PRNGKey(4), cfg)["layers"][0]
    row = (1, 0, 1, 0)
    from repro.core.quant import prune_block_heads
    back = expand_block_heads(prune_block_heads(dense, row), row)
    dh = cfg.dim // cfg.heads
    assert back["wq"].shape == dense["wq"].shape
    assert jnp.array_equal(back["wq"][0], dense["wq"][0])
    assert jnp.array_equal(back["wq"][1], jnp.zeros_like(dense["wq"][1]))
    assert jnp.array_equal(back["wq"][2], dense["wq"][2])
    rows = back["w_msa"].reshape(cfg.heads, dh, cfg.dim)
    assert jnp.array_equal(rows[1], jnp.zeros_like(rows[1]))
    assert jnp.array_equal(rows[3], jnp.zeros_like(rows[3]))
    # surviving concat rows carry the folded H/K rescale (here 4/2 = 2)
    assert jnp.array_equal(
        rows[0], dense["w_msa"].reshape(cfg.heads, dh, cfg.dim)[0] * 2.0)
