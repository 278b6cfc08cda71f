"""Property tests for the vision param-spec head-shard ladder.

`distributed.sharding.vision_param_specs` is the single source of truth
for WHERE the 2-D (data, model) mesh splits the vision models — the
executor (`core.schedule.ShardCtx`) reads the spec tree back to decide
where its `shard_map` all-reduces fire — so these invariants are
load-bearing for correctness, not just placement hygiene:

  * divisibility ladder: a head count that does not divide the model
    axis degrades to replication (never a compile error, never a
    half-sharded attention block);
  * int8 per-head scales shard with their values (a scale placed
    differently from its values would dequantize the wrong head slice);
  * the MLP column/row pair moves as one unit — w_up columns, b_up and
    w_down rows all sharded or all replicated (the psum at the residual
    re-entry is only correct when the pair agrees);
  * specs are a function of (path names, shapes) alone — stable under
    param-pytree re-ordering.

Via tests/_hypothesis_compat.py: real `hypothesis` when installed, a
deterministic seeded endpoint-inclusive sweep otherwise.  Pure spec
algebra on abstract meshes / ShapeDtypeStruct trees: no devices needed,
so the matrix runs identically on the dev-1 and dev-8 CI legs.
"""

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh

from _hypothesis_compat import given, settings, strategies as st

from repro.core.quant import QTensor
from repro.distributed import sharding as shd

SDS = jax.ShapeDtypeStruct


def _block(heads: int, dh: int, hidden: int, dim: int = None):
    """One attention+MLP block's float param subtree, head-major concat
    projection (dim == heads*dh) unless ``dim`` overrides it."""
    dim = heads * dh if dim is None else dim
    f = jnp.float32
    return {
        "wq": SDS((heads, dim, dh), f),
        "wk": SDS((heads, dim, dh), f),
        "wv": SDS((heads, dim, dh), f),
        "w_msa": SDS((dim, dim), f),
        "ln1_w": SDS((dim,), f), "ln1_b": SDS((dim,), f),
        "ln2_w": SDS((dim,), f), "ln2_b": SDS((dim,), f),
        "w_up": SDS((dim, hidden), f),
        "b_up": SDS((hidden,), f),
        "w_down": SDS((hidden, dim), f),
        "b_down": SDS((dim,), f),
    }


def _qblock(heads: int, dh: int, hidden: int):
    """The int8 PTQ twin: QTensor leaves with the real quantizer's scale
    layouts — per-head (H, 1, Dh) on the stacks, per-out-channel (1, n)
    on the 2-D mats."""
    dim = heads * dh
    b = _block(heads, dh, hidden)

    def q(name, vshape, sshape):
        b[name] = QTensor(SDS(vshape, jnp.int8), SDS(sshape, jnp.float32))
    for n in ("wq", "wk", "wv"):
        q(n, (heads, dim, dh), (heads, 1, dh))
    q("w_msa", (dim, dim), (1, dim))
    q("w_up", (dim, hidden), (1, hidden))
    q("w_down", (hidden, dim), (1, dim))
    return b


def _mesh2(model: int):
    return AbstractMesh((2, model), ("data", "model"))


def _spec(tree, model: int):
    return shd.vision_param_specs({"layers": [tree]}, _mesh2(model))[
        "layers"][0]


# ---------------------------------------------------------------------------
# Property: divisibility ladder + block coherence
# ---------------------------------------------------------------------------


@settings(max_examples=40)
@given(st.integers(min_value=1, max_value=12),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=64))
def test_head_ladder_divisibility_and_coherence(heads, model, dh, hidden):
    """H % M == 0 shards the whole attention unit (stacks + concat
    projection rows), anything else replicates the whole unit; the MLP
    pair shards iff hidden % M == 0, always as one unit."""
    spec = _spec(_block(heads, dh, hidden), model)
    att_sharded = heads % model == 0
    want = ("model", None, None) if att_sharded else (None, None, None)
    for n in ("wq", "wk", "wv"):
        assert tuple(spec[n]) == want, (n, heads, model)
    assert tuple(spec["w_msa"]) == (
        ("model", None) if att_sharded else ()), (heads, model)
    mlp_sharded = hidden % model == 0
    assert tuple(spec["w_up"]) == (
        (None, "model") if mlp_sharded else (None, None))
    assert tuple(spec["b_up"]) == (("model",) if mlp_sharded else (None,))
    assert tuple(spec["w_down"]) == (
        ("model", None) if mlp_sharded else (None, None))
    # residuals / norms never shard (they re-enter on every device)
    for n in ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "b_down"):
        assert tuple(spec[n]) == (), n


@settings(max_examples=40)
@given(st.integers(min_value=1, max_value=12),
       st.integers(min_value=2, max_value=8),
       st.integers(min_value=1, max_value=4))
def test_qtensor_scales_follow_their_values(heads, model, dh):
    """Per-head (H, 1, Dh) scales take the SAME spec as their (H, D, Dh)
    values — sharded heads carry their scales; contraction-side (1, n)
    scales on row-sharded mats replicate (they scale the full-width
    partial, which commutes with the psum)."""
    hidden = 4 * heads * dh
    spec = _spec(_qblock(heads, dh, hidden), model)
    for n in ("wq", "wk", "wv"):
        assert tuple(spec[n].values) == tuple(spec[n].scale), (n, heads,
                                                               model)
    # w_up: per-out-channel (1, hidden) scale shards its channel dim
    # exactly when the values' column dim does
    assert tuple(spec["w_up"].scale)[-1] == tuple(spec["w_up"].values)[-1]
    # w_down values may row-shard; its (1, C) scale must NOT (dim 0 is 1:
    # the _fits ladder can never divide it across model > 1)
    assert "model" not in tuple(spec["w_down"].scale)
    # w_msa (1, C) scale likewise replicates even when values row-shard
    assert "model" not in tuple(spec["w_msa"].scale)


@settings(max_examples=30)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=2, max_value=8))
def test_specs_stable_under_pytree_reordering(heads, model):
    """Specs depend on (path names, shapes) only: reversing dict
    insertion order and block list order must permute the spec tree the
    same way, never change any leaf's spec."""
    dh, hidden = 2, 4 * heads * 2
    a = _block(heads, dh, hidden)
    b = _block(heads + 1, dh, hidden + 1)
    fwd = shd.vision_param_specs({"layers": [a, b]}, _mesh2(model))
    rev_blocks = {k: a[k] for k in reversed(list(a))}
    rev = shd.vision_param_specs({"layers": [rev_blocks, b]},
                                 _mesh2(model))
    for k in a:
        assert tuple(fwd["layers"][0][k]) == tuple(rev["layers"][0][k]), k
    swapped = shd.vision_param_specs({"layers": [b, a]}, _mesh2(model))
    for k in a:
        assert tuple(swapped["layers"][1][k]) == \
            tuple(fwd["layers"][0][k]), k
        assert tuple(swapped["layers"][0][k]) == \
            tuple(fwd["layers"][1][k]), k


# ---------------------------------------------------------------------------
# Point cases the properties can't reach
# ---------------------------------------------------------------------------


def test_w_msa_replicates_when_concat_dim_is_not_head_major():
    """A concat projection whose row count != H*Dh (e.g. a block whose
    channel dim is padded) must replicate even with divisible heads —
    row blocks would not match the local heads' concat slice."""
    blk = _block(4, 2, 32, dim=12)           # dim 12 != 4*2
    spec = _spec(blk, 2)
    assert tuple(spec["wq"]) == ("model", None, None)   # heads shard...
    assert tuple(spec["w_msa"]) == ()                   # ...rows do not


def test_no_model_axis_means_fully_replicated():
    """On the 1-D data mesh every leaf replicates (the GSPMD serving
    path) — the model-axis ladder must not leak in."""
    mesh = AbstractMesh((8,), ("data",))
    specs = shd.vision_param_specs(
        {"layers": [_block(4, 2, 32)]}, mesh)
    for leaf in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, shd.P)):
        assert tuple(leaf) == ()
