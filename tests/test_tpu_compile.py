"""Compile rehearsals for a described TPU v5e: the served kernels and
forwards at published widths, compiled (not interpreted) by the TPU
compiler for a chip that is described and not attached.

Interpret-mode tests (test_kernels.py and friends) check the numbers;
these check what only the chip's compiler refuses: block shapes that
break the (8, 128) tiling rule, kernels that cannot be partitioned, and
kernels that do not fit VMEM.  Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture, never at import: the
TPU library admits one process at a time, and only the pytest worker
that runs this file may load it.  Every compile here is of the kernel
or jitted forward itself, with ``ops._ON_TPU`` patched so that the
dispatch layer emits real kernels instead of interpreting them.
"""

import collections
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import schedule as sched_lib
from repro.kernels import ops
from repro.kernels.vita_layer import (vita_layer, vita_layer_group,
                                      vita_layer_group_int8,
                                      vita_layer_int8)
from repro.kernels.vita_msa import vita_msa_batched, vita_msa_int8
from repro.models import vision_registry, vit

F32, I8 = jnp.float32, jnp.int8

# (tokens N, width D, heads H, MLP hidden M) of the served geometries.
DEIT_T = dict(n=196, d=192, h=3, m=768)             # DeiT-Ti/16 at 224
SWIN_T_S0 = dict(n=49, d=96, h=3, m=384)            # Swin-T stage 0 window
TNT_S_INNER = dict(n=16, d=24, h=4, m=96)           # TNT-S pixel stream


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """Steer the dispatch layer to real kernels, and keep the persistent
    compile cache out of it (a TPU entry cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_ON_TPU", True)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _layer_args(s, b, n, d, h, m, *, layers=None, wdtype=F32):
    dh = d // h
    lead = () if layers is None else (layers,)
    return [_spec(s, (b, n, d)),
            _spec(s, lead + (h, d, dh), wdtype),
            _spec(s, lead + (h, d, dh), wdtype),
            _spec(s, lead + (h, d, dh), wdtype),
            _spec(s, lead + (d, d), wdtype)]


def _float_tail(s, d, m, lead=()):
    # ln1_w, ln1_b, ln2_w, ln2_b, w_up, b_up, w_down, b_down
    return [_spec(s, lead + (d,)), _spec(s, lead + (d,)),
            _spec(s, lead + (d,)), _spec(s, lead + (d,)),
            _spec(s, lead + (d, m)), _spec(s, lead + (m,)),
            _spec(s, lead + (m, d)), _spec(s, lead + (d,))]


def _int8_args(s, b, n, d, h, m, lead=()):
    dh = d // h
    return ([_spec(s, (b, n, d))]
            + [_spec(s, lead + (h, d, dh), I8)] * 3
            + [_spec(s, lead + (d, d), I8), _spec(s, lead + (d, m), I8),
               _spec(s, lead + (m, d), I8),
               _spec(s, lead + (4,))]
            + [_spec(s, lead + (h, dh))] * 3
            + [_spec(s, lead + (1, d)), _spec(s, lead + (1, m)),
               _spec(s, lead + (1, d))]
            + [_spec(s, lead + (d,))] * 4
            + [_spec(s, lead + (m,)), _spec(s, lead + (d,))])


def test_vita_msa_batched_deit_t(one_chip):
    n, d, h = DEIT_T["n"], DEIT_T["d"], DEIT_T["h"]
    w = _spec(one_chip, (h, d, d // h))
    _compile(vita_msa_batched, _spec(one_chip, (8, n, d)), w, w, w,
             interpret=False)


@pytest.mark.parametrize("dims,batch,windows", [
    (DEIT_T, 8, 0),
    (SWIN_T_S0, 2 * 64, 64),
    (TNT_S_INNER, 2 * 196, 0),
], ids=["deit_t", "swin_t_stage0_windowed", "tnt_s_inner"])
def test_vita_layer(one_chip, dims, batch, windows):
    s = one_chip
    n, d, h, m = dims["n"], dims["d"], dims["h"], dims["m"]
    args = _layer_args(s, batch, n, d, h, m) + _float_tail(s, d, m)
    if windows:
        args += [_spec(s, (h, n, n)), _spec(s, (windows, n, n))]
    _compile(vita_layer, *args, interpret=False)


def test_vita_msa_int8_deit_t(one_chip):
    s = one_chip
    n, d, h = DEIT_T["n"], DEIT_T["d"], DEIT_T["h"]
    w = _spec(s, (h, d, d // h), I8)
    sc = _spec(s, (h, d // h))
    _compile(vita_msa_int8, _spec(s, (8, n, d), I8), w, w, w, _spec(s, ()),
             sc, sc, sc, interpret=False)


def test_vita_layer_int8_deit_t(one_chip):
    n, d, h, m = DEIT_T["n"], DEIT_T["d"], DEIT_T["h"], DEIT_T["m"]
    _compile(vita_layer_int8, *_int8_args(one_chip, 8, n, d, h, m),
             interpret=False)


def test_vita_layer_group_deit_t(one_chip):
    s = one_chip
    n, d, h, m = DEIT_T["n"], DEIT_T["d"], DEIT_T["h"], DEIT_T["m"]
    args = (_layer_args(s, 8, n, d, h, m, layers=2)
            + _float_tail(s, d, m, lead=(2,)))
    _compile(vita_layer_group, *args, interpret=False)


def test_vita_layer_group_int8_deit_t(one_chip):
    n, d, h, m = DEIT_T["n"], DEIT_T["d"], DEIT_T["h"], DEIT_T["m"]
    _compile(vita_layer_group_int8,
             *_int8_args(one_chip, 8, n, d, h, m, lead=(2,)),
             interpret=False)


def _frozen_calibrator():
    """int8 forwards read frozen per-site scales; any value compiles."""
    from repro.core.quant import Calibrator
    cal = Calibrator()
    cal.frozen = collections.defaultdict(lambda: jnp.asarray(0.05, F32))
    return cal


def _full_shapes(name, sharding, mode="float"):
    cfg = vision_registry.build_cfg(name, full=True, backend="pallas")
    params = jax.eval_shape(
        lambda: vision_registry.init_params(jax.random.PRNGKey(0), cfg))
    if mode == "int8":
        params = jax.eval_shape(vision_registry.quantize, params)
    params = jax.tree.map(lambda x: _spec(sharding, x.shape, x.dtype),
                          params)
    return cfg, params


@pytest.mark.parametrize("mode", ["float", "int8"])
def test_deit_t_full_forward(one_chip, mode):
    """The whole served DeiT-Ti forward (224 px, 12 layers) on one chip."""
    cfg, params = _full_shapes("deit_t", one_chip, mode)
    obs = _frozen_calibrator() if mode == "int8" else None

    def fwd(p, images):
        return vit.forward(p, vit.extract_patches(images, cfg.patch), cfg,
                           observer=obs)

    text = _compile(fwd, params, _spec(one_chip, (8, 224, 224, 3)))
    # one fused layer kernel per encoder layer, none interpreted
    assert text.count("tpu_custom_call") >= cfg.layers


def test_swin_t_full_forward(one_chip):
    """The whole served Swin-T float forward (224 px, depths 2/2/6/2,
    widths 96-768) at bucket 32 on one chip: every layer a windowed
    fused kernel over 32 x (64, 16, 4, 1) windows of 49 tokens."""
    cfg, params = _full_shapes("swin_t", one_chip)

    def fwd(p, images):
        return vision_registry.forward_fn(cfg)(
            p, vit.extract_patches(images, cfg.patch), cfg)

    text = _compile(fwd, params, _spec(one_chip, (32, 224, 224, 3)))
    # one fused layer kernel per block, none interpreted
    assert text.count("tpu_custom_call") == sum(cfg.depths)


@pytest.mark.parametrize("mode", ["float", "int8"])
def test_deit_t_data_mesh_forward(topo, mode):
    """The 4-chip data-parallel forward: kernels run per shard under
    shard_map (GSPMD cannot partition a Mosaic kernel)."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    cfg, params = _full_shapes("deit_t", NamedSharding(mesh, P()), mode)
    sched = vision_registry.make_schedule(cfg)
    fn = sched_lib.build_sharded_fn(
        sched, params, mesh, batch=8,
        observer=_frozen_calibrator() if mode == "int8" else None,
        preprocess=lambda im: vit.extract_patches(im, cfg.patch), x_ndim=4)
    images = _spec(NamedSharding(mesh, P("data")), (8, 224, 224, 3))
    _compile(fn, params, images)
