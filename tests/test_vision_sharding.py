"""Data-parallel vision serving: sharding rules (no devices needed) plus
multi-device parity/padding/fallback tests that self-skip on a
single-device host (CI's dev-1 matrix leg; locally run them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.core import schedule as sched_lib
from repro.core.quant import QTensor, ptq_tolerance
from repro.distributed import sharding as shd
from repro.launch.vision_serve import (ServeConfig, VisionServer,
                                       calibrate, round_buckets)
from repro.launch.vision_serve import main as vision_serve_main
from repro.models import vision_registry, vit

NDEV = jax.device_count()
needs_multi = pytest.mark.skipif(
    NDEV < 2, reason="needs >=2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs_four = pytest.mark.skipif(
    NDEV < 4, reason="needs >=4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
needs_eight = pytest.mark.skipif(
    NDEV < 8, reason="needs >=8 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _mesh(n):
    from repro.launch.mesh import make_vision_mesh
    return make_vision_mesh(n)


def _sorted_logits(server):
    return np.stack([r.logits for r in
                     sorted(server.done, key=lambda r: r.rid)])


# ---------------------------------------------------------------------------
# Rule set (abstract mesh — runs on any host, including the dev-1 CI leg)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", vision_registry.list_models())
def test_vision_params_replicate_over_data(name):
    """Serving is data-parallel: no param leaf — float weight, int8 values
    or quantization scale — may shard over the ``data`` axis, for any
    registered family's tree layout."""
    cfg = vision_registry.build_cfg(name)
    mesh = AbstractMesh((8,), ("data",))
    for tree in (
            jax.eval_shape(lambda: vision_registry.init_params(
                jax.random.PRNGKey(0), cfg)),
            jax.eval_shape(lambda: vision_registry.quantize(
                vision_registry.init_params(jax.random.PRNGKey(0), cfg)))):
        specs = shd.vision_param_specs(tree, mesh)
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, shd.P))
        assert leaves, name
        for spec in leaves:
            flat = [a for ax in tuple(spec) if ax is not None
                    for a in (ax if isinstance(ax, tuple) else (ax,))]
            assert "data" not in flat, (name, spec)


def test_vision_per_head_specs_use_fits_fallback():
    """On a mesh WITH a model axis, per-head wq/wk/wv stacks shard their
    head dim when it divides, degrading to replication when it doesn't —
    the LM rules' `_fits` ladder, reused."""
    cfg = vision_registry.build_cfg("vit_edge")      # heads=4
    pshape = jax.eval_shape(lambda: vision_registry.init_params(
        jax.random.PRNGKey(0), cfg))
    qshape = jax.eval_shape(lambda: vision_registry.quantize(
        vision_registry.init_params(jax.random.PRNGKey(0), cfg)))
    mesh2 = AbstractMesh((4, 2), ("data", "model"))
    mesh16 = AbstractMesh((2, 16), ("data", "model"))
    for tree in (pshape, qshape):
        spec2 = shd.vision_param_specs(tree, mesh2)
        spec16 = shd.vision_param_specs(tree, mesh16)
        wq2 = spec2["layers"][0]["wq"]
        wq16 = spec16["layers"][0]["wq"]
        if isinstance(wq2, QTensor):
            # int8: the (H, D, Dh) values AND the (H, 1, Dh) per-head
            # scale shard the head dim together
            assert tuple(wq2.values) == ("model", None, None)
            assert tuple(wq2.scale) == ("model", None, None)
            assert tuple(wq16.values) == (None, None, None)  # 4 % 16 != 0
        else:
            assert tuple(wq2) == ("model", None, None)
            assert tuple(wq16) == (None, None, None)         # 4 % 16 != 0


def test_vision_batch_spec_divisibility_fallback():
    mesh = AbstractMesh((4,), ("data",))
    assert tuple(shd.vision_batch_spec(8, mesh)) == ("data",)
    assert tuple(shd.vision_batch_spec(5, mesh)) in ((None,), ())


def test_round_buckets():
    assert round_buckets((1, 2, 4, 8), 1) == (1, 2, 4, 8)
    assert round_buckets((1, 2, 4, 8), 4) == (4, 8)
    assert round_buckets((1, 2, 4), 8) == (8,)
    assert round_buckets((3, 5), 4) == (4, 8)


def test_parse_mesh_shape():
    from repro.launch.mesh import parse_mesh_shape
    assert parse_mesh_shape("4x2") == (4, 2)
    assert parse_mesh_shape("8") == (8, 1)       # bare count = 1-D mesh
    assert parse_mesh_shape("2×4") == (2, 4)   # unicode multiply sign
    assert parse_mesh_shape((2, 4)) == (2, 4)
    for bad in ("abc", "0x4", "4x-2", "1x2x3"):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


def test_single_device_server_unchanged(tiny_vit):
    """data_parallel=1 (the default) must not build a mesh or touch the
    buckets — the dev-1 CI leg serves exactly the old path."""
    cfg, params, images = tiny_vit
    server = VisionServer(
        cfg, params,
        serve_cfg=ServeConfig(buckets=(1, 2, 4), data_parallel=1))
    assert server.mesh is None and server.dp == 1
    assert server.buckets == (1, 2, 4)
    server.submit_many(images[:3])
    stats = server.run()
    assert stats["requests"] == 3 and stats["devices"] == 1


@pytest.fixture(scope="module")
def tiny_vit():
    from repro.launch.vision_serve import build_edge_vit
    cfg = build_edge_vit(image=16, patch=8, dim=48, heads=4, layers=2,
                         n_classes=10)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    images = np.random.default_rng(0).standard_normal(
        (5, cfg.image, cfg.image, 3)).astype(np.float32)
    return cfg, params, images


def test_run_stats_do_not_mix_prior_runs(tiny_vit):
    """run() on an already-drained server must report zeros (same schema),
    not recompute percentiles over every PRIOR run's requests."""
    cfg, params, images = tiny_vit
    server = VisionServer(cfg, params,
                          serve_cfg=ServeConfig(buckets=(1, 2, 4)))
    server.submit_many(images)
    first = server.run()
    assert first["requests"] == len(images)
    idle = server.run()                    # queue already empty
    assert idle["requests"] == 0 and idle["batches"] == 0
    assert idle["latency_p50_ms"] == 0.0 and idle["latency_p99_ms"] == 0.0
    assert idle["latency_mean_ms"] == 0.0 and idle["throughput_img_s"] == 0.0
    assert set(idle) == set(first)         # same row schema either way


# ---------------------------------------------------------------------------
# Multi-device (self-skip on single-device hosts)
# ---------------------------------------------------------------------------


@needs_multi
@pytest.mark.parametrize("name", vision_registry.list_models())
def test_sharded_serving_parity_every_model(name):
    """Float AND int8 drains over the full device mesh match the
    single-device server within the PTQ gate (float is near-bitwise)."""
    cfg = vision_registry.build_cfg(name)
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vision_registry.quantize(params)
    images = np.random.default_rng(1).standard_normal(
        (5, cfg.image, cfg.image, 3)).astype(np.float32)
    cal = calibrate(qparams, cfg, images[:2], n_batches=1)
    for mode in ("float", "int8"):
        out = {}
        for dp in (1, NDEV):
            server = VisionServer(
                cfg, params, qparams=qparams, calibrator=cal,
                serve_cfg=ServeConfig(mode=mode, buckets=(1, 2, 4, 8),
                                      data_parallel=dp))
            server.submit_many(images)
            stats = server.run()
            assert stats["requests"] == len(images)
            assert stats["devices"] == dp
            out[dp] = _sorted_logits(server)
        err = np.abs(out[NDEV] - out[1]).max()
        scale = np.abs(out[1]).max()
        assert err <= ptq_tolerance(scale), (name, mode, err, scale)
        if mode == "float":
            np.testing.assert_allclose(out[NDEV], out[1],
                                       rtol=1e-4, atol=1e-4)


@needs_four
def test_padding_path_five_requests_four_devices():
    """5 requests on 4 devices: default buckets round to (4, 8), the drain
    takes all 5, pads to bucket 8, and unpads logits per request."""
    cfg = vision_registry.build_cfg("vit_edge")
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    images = np.random.default_rng(2).standard_normal(
        (5, cfg.image, cfg.image, 3)).astype(np.float32)
    server = VisionServer(
        cfg, params,
        serve_cfg=ServeConfig(buckets=(1, 2, 4, 8), mesh=_mesh(4)))
    assert server.buckets == (4, 8)
    reqs = server.submit_many(images)
    stats = server.run()
    assert stats["requests"] == 5 and stats["devices"] == 4
    assert stats["batches"] == 1 and stats["padded"] == 3
    solo = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(1,)))
    solo.submit(images[3])
    solo.run()
    np.testing.assert_allclose(reqs[3].logits, solo.done[0].logits,
                               rtol=1e-4, atol=1e-4)


@needs_multi
def test_non_divisible_mesh_falls_back_to_replication():
    """A mesh whose size divides no bucket must degrade to replication
    (vision_batch_spec -> P(None)), not die in GSPMD."""
    n = 3 if NDEV >= 3 else 2
    mesh = _mesh(n)
    cfg = vision_registry.build_cfg("vit_edge")
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    patches = vit.extract_patches(
        jnp.asarray(np.random.default_rng(3).standard_normal(
            (n + 1, cfg.image, cfg.image, 3)).astype(np.float32)),
        cfg.patch)
    assert patches.shape[0] % n != 0
    sched = vision_registry.make_schedule(cfg)
    ref = np.asarray(sched_lib.run_schedule(sched, params, patches))
    out = np.asarray(sched_lib.run_schedule_sharded(
        sched, params, patches, mesh))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@needs_multi
@pytest.mark.parametrize("fused", [True, False])
def test_run_schedule_sharded_fused_and_unfused(fused):
    """The mesh-aware executor entry places both the fused `layer`-phase
    grid and the per-phase grid under NamedSharding with equal logits."""
    cfg = vision_registry.build_cfg("swin_t", fused=fused)
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    patches = vit.extract_patches(
        jnp.asarray(np.random.default_rng(4).standard_normal(
            (NDEV, cfg.image, cfg.image, 3)).astype(np.float32)),
        cfg.patch)
    sched = vision_registry.make_schedule(cfg)
    ref = np.asarray(sched_lib.run_schedule(sched, params, patches))
    out = np.asarray(sched_lib.run_schedule_sharded(
        sched, params, patches, _mesh(NDEV)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@needs_multi
def test_cli_devices_roundtrip(capsys):
    """serve.py --vision --devices N end-to-end through the CLI."""
    stats = vision_serve_main(["--model", "vit_edge", "--devices", "2",
                               "--requests", "4", "--mode", "float",
                               "--buckets", "1,2,4"])
    assert stats and all(s["devices"] == 2 for s in stats)
    assert sum(s["requests"] for s in stats) == 4


# ---------------------------------------------------------------------------
# 2-D (data, model) mesh (self-skip below 8 devices)
# ---------------------------------------------------------------------------


@needs_eight
def test_bucket_rounding_uses_data_axis_not_device_count(tiny_vit):
    """REGRESSION: on a (2, 4) mesh only 2 batch shards exist, so buckets
    must round to multiples of the DATA-axis size (2), not the total
    device count (8) — rounding 2 up to 8 would pad every drain 4x."""
    cfg, params, _ = tiny_vit
    server = VisionServer(
        cfg, params,
        serve_cfg=ServeConfig(buckets=(2, 4, 8), mesh_shape="2x4"))
    assert (server.dp, server.mp, server.n_devices) == (2, 4, 8)
    assert server.buckets == (2, 4, 8)       # NOT (8,)
    assert server.mesh_shape == "2x4"


@needs_eight
def test_batch1_bucket_survives_on_model_mesh(tiny_vit):
    """A requested bucket 1 must survive on a 2-D mesh (the batch=1
    latency fast path: batch replicates over ``data``, heads still split
    over ``model``) even though data-axis rounding would lift it."""
    cfg, params, _ = tiny_vit
    server = VisionServer(
        cfg, params,
        serve_cfg=ServeConfig(buckets=(1, 4), mesh_shape="4x2"))
    assert (server.dp, server.mp) == (4, 2)
    assert server.buckets == (1, 4)
    server.submit(np.zeros((cfg.image, cfg.image, 3), np.float32))
    stats = server.run()
    assert stats["batches"] == 1 and stats["padded"] == 0


@needs_eight
def test_two_d_mesh_server_drain_parity(tiny_vit):
    """A full drain through the (2, 4) mesh — head-sharded MSA +
    column-sharded MLP under shard_map — matches the single-device
    server."""
    cfg, params, images = tiny_vit
    solo = VisionServer(cfg, params,
                          serve_cfg=ServeConfig(buckets=(1, 2, 4)))
    solo.submit_many(images)
    solo.run()
    server = VisionServer(
        cfg, params,
        serve_cfg=ServeConfig(buckets=(1, 2, 4), mesh_shape="2x4"))
    server.submit_many(images)
    stats = server.run()
    assert stats["requests"] == len(images)
    assert stats["devices"] == 8 and stats["mesh_shape"] == "2x4"
    np.testing.assert_allclose(_sorted_logits(server),
                               _sorted_logits(solo), rtol=1e-4, atol=1e-4)


@needs_eight
def test_cli_mesh_roundtrip(capsys):
    """serve.py --vision --mesh DxM end-to-end through the CLI."""
    stats = vision_serve_main(["--model", "vit_edge", "--mesh", "4x2",
                               "--requests", "4", "--mode", "float",
                               "--buckets", "4"])
    assert stats and all(s["mesh_shape"] == "4x2" for s in stats)
    assert all(s["devices"] == 8 for s in stats)
    assert sum(s["requests"] for s in stats) == 4
