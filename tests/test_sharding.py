"""Sharding-rule tests (AbstractMesh, no devices needed) + a tiny-mesh
dry-run integration test run in a subprocess (device-count isolation)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro import configs
from repro.distributed import sharding as shd
from repro.models import transformer as tr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def production_abstract_mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes)


@pytest.mark.parametrize("arch", configs.list_archs())
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_always_divisible(arch, multi_pod):
    """Every spec produced by the rules divides its dim by the mesh axis —
    the divisibility-fallback invariant across ALL archs."""
    cfg = configs.get(arch)
    mesh = production_abstract_mesh(multi_pod)
    pshape = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(cfg, pshape, mesh)
    axis = dict(zip(mesh.axis_names, mesh.axis_sizes))

    flat_l, treedef = jax.tree_util.tree_flatten(pshape)
    flat_s = treedef.flatten_up_to(specs)
    n_sharded = 0
    for leaf, spec in zip(flat_l, flat_s):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            size = int(np.prod([axis[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))]))
            assert dim % size == 0, (arch, leaf.shape, spec)
            n_sharded += 1
    assert n_sharded > 0, f"{arch}: nothing sharded at all"


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "mixtral-8x7b",
                                  "internvl2-26b"])
def test_big_matrices_are_model_sharded(arch):
    """The big 2D weights must actually shard over the model axis (TP) —
    replicated 32B params would never fit 16 GB/chip."""
    cfg = configs.get(arch)
    mesh = production_abstract_mesh()
    pshape = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg))
    specs = shd.param_specs(cfg, pshape, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    shapes = jax.tree_util.tree_flatten_with_path(pshape)[0]
    replicated_big = []
    for (path, spec), (_, leaf) in zip(flat, shapes):
        n = int(np.prod(leaf.shape))
        if n >= 16 * 2 ** 20 and all(ax is None for ax in tuple(spec)):
            replicated_big.append(
                ("/".join(str(getattr(p, 'key', p)) for p in path),
                 leaf.shape))
    assert not replicated_big, replicated_big


def test_moe_ep_vs_tp_choice():
    """olmoe (64 experts) -> expert-parallel; mixtral (8) -> TP in expert."""
    mesh = production_abstract_mesh()
    for arch, expect_ep in [("olmoe-1b-7b", True), ("mixtral-8x7b", False)]:
        cfg = configs.get(arch)
        pshape = jax.eval_shape(
            lambda c=cfg: tr.init_params(jax.random.PRNGKey(0), c))
        specs = shd.param_specs(cfg, pshape, mesh)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        for path, spec in flat:
            keys = [str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path]
            if "moe" in keys and keys[-1] == "w_up":
                t = tuple(spec)
                if expect_ep:
                    assert t[1] == "model", (arch, t)   # expert dim sharded
                else:
                    assert t[1] is None and "model" in t, (arch, t)


def _bytes_per_device(shape_tree, spec_tree, mesh):
    axis = dict(zip(mesh.axis_names, mesh.axis_sizes))
    total = 0
    flat_l, treedef = jax.tree_util.tree_flatten(shape_tree)
    flat_s = treedef.flatten_up_to(spec_tree)
    for leaf, spec in zip(flat_l, flat_s):
        denom = int(np.prod([
            axis[a] for ax in tuple(spec) if ax is not None
            for a in (ax if isinstance(ax, tuple) else (ax,))]))
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // denom
    return total


def test_state_bytes_fit_hbm():
    """Params (bf16, TP) + Adam moments (fp32, ZeRO-1 over data) fit a
    16 GB v5e chip for every arch on the single-pod mesh."""
    mesh = production_abstract_mesh()
    for arch in configs.list_archs():
        cfg = configs.get(arch)
        pshape = jax.eval_shape(
            lambda c=cfg: tr.init_params(jax.random.PRNGKey(0), c))
        pspec = shd.param_specs(cfg, pshape, mesh)
        p_bytes = _bytes_per_device(pshape, pspec, mesh)
        mom_spec = shd.opt_state_specs(pspec, pshape, mesh)["m"]
        mom_shape = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), pshape)
        m_bytes = _bytes_per_device(mom_shape, mom_spec, mesh)
        total = p_bytes + 2 * m_bytes
        assert total < 12e9, (arch, total / 1e9)


def test_zero1_moments_sharded_over_data():
    """ZeRO-1: mixtral moments must gain a data-axis dim vs param specs."""
    mesh = production_abstract_mesh()
    cfg = configs.get("mixtral-8x7b")
    pshape = jax.eval_shape(
        lambda: tr.init_params(jax.random.PRNGKey(0), cfg))
    pspec = shd.param_specs(cfg, pshape, mesh)
    mspec = shd.opt_state_specs(pspec, pshape, mesh)["m"]
    n_data = sum("data" in tuple(s) for s in jax.tree_util.tree_leaves(
        mspec, is_leaf=lambda x: isinstance(x, shd.P)))
    assert n_data > 10, n_data


def test_batch_axis_fallbacks():
    mesh = production_abstract_mesh(multi_pod=True)
    assert shd._batch_axis(256, mesh) == ("pod", "data")   # 256 % 32 == 0
    assert shd._batch_axis(16, mesh) == "data"             # only data fits
    assert shd._batch_axis(1, mesh) is None                # replicate


@pytest.mark.slow
def test_dryrun_debug_mesh_subprocess():
    """End-to-end dry-run machinery on a small forced-device-count mesh,
    in a subprocess so the main test process keeps its 1 CPU device."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, os.path.join(%r, "src"))
from repro.launch import dryrun as dr
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(model=2, data=2, multi_pod=True)  # 2x2x2 = 8
rec = dr.lower_cell("h2o-danube-1.8b", "decode_32k", mesh)
assert rec["hlo_flops_per_device"] and rec["hlo_flops_per_device"] > 0
assert rec["collectives"]["op_count"] >= 0
print(json.dumps({"ok": True,
                  "flops": rec["hlo_flops_per_device"],
                  "coll": rec["collectives"]["bytes_total"]}))
""" % REPO
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"]


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
  %ag = bf16[32,128]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[64]{0} all-reduce(%y), replica_groups=[8,4]<=[32], to_apply=%sum
  %cp = bf16[16,16]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
"""
    stats = parse_collectives(hlo, 32)
    assert stats["op_count"] == 3
    ag = 32 * 128 * 2 * 3 // 4          # (gs-1)/gs * bytes
    ar = int(2 * 3 / 4 * 64 * 4)
    cp = 16 * 16 * 2
    assert stats["by_kind"]["all-gather"] == ag
    assert stats["by_kind"]["all-reduce"] == ar
    assert stats["by_kind"]["collective-permute"] == cp
    assert stats["by_group_size"]["4"] == ag + ar
