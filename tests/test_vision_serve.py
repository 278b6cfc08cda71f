"""VisionServer micro-batching driver: drain semantics, bucket padding,
latency bookkeeping, float-vs-int8 PTQ agreement, and a round-trip through
every model in the vision registry (one pipeline, many control programs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import ptq_tolerance
from repro.launch.vision_serve import (ServeConfig, VisionRequest,
                                       VisionServer, build_edge_vit,
                                       calibrate)
from repro.models import vision_registry, vit


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = build_edge_vit(image=16, patch=8, dim=48, heads=4, layers=2,
                         n_classes=10)
    params = vit.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((11, cfg.image, cfg.image, 3)
                                 ).astype(np.float32)
    return cfg, params, images


def test_all_requests_drain_with_latency(tiny_setup):
    cfg, params, images = tiny_setup
    server = VisionServer(cfg, params,
                          serve_cfg=ServeConfig(buckets=(1, 2, 4)))
    reqs = server.submit_many(images)
    stats = server.run()
    assert stats["requests"] == len(images)
    assert not server.queue and len(server.done) == len(images)
    for r in reqs:
        assert r.t_done is not None and r.pred is not None
        assert 0 <= r.pred < cfg.n_classes
        assert r.latency_s >= 0
    assert stats["throughput_img_s"] > 0
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"] > 0
    # 11 requests over max bucket 4: 4 + 4 + 3-padded-to-4 = 3 batches
    assert stats["batches"] == 3 and stats["padded"] == 1


def test_bucket_padding(tiny_setup):
    cfg, params, images = tiny_setup
    server = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(4,)))
    server.submit_many(images[:3])
    stats = server.run()
    assert stats["requests"] == 3
    assert stats["padded"] == 1          # 3 requests padded up to bucket 4
    # padding must not perturb the real requests' logits
    solo = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(1,)))
    solo.submit(images[0])
    solo.run()
    np.testing.assert_allclose(server.done[0].logits, solo.done[0].logits,
                               rtol=1e-5, atol=1e-5)


def test_int8_and_float_agree_within_ptq_tolerance(tiny_setup):
    cfg, params, images = tiny_setup
    qparams = vit.quantize_vit(params)
    cal = calibrate(qparams, cfg, images[:8])

    results = {}
    for mode in ("float", "int8"):
        server = VisionServer(
            cfg, params, qparams=qparams, calibrator=cal,
            serve_cfg=ServeConfig(mode=mode, buckets=(1, 2, 4)))
        server.submit_many(images)
        stats = server.run()
        assert stats["requests"] == len(images)
        results[mode] = np.stack([r.logits for r in server.done])
    scale = np.abs(results["float"]).max()
    err = np.abs(results["float"] - results["int8"]).max()
    assert err <= ptq_tolerance(scale), (err, scale)


def test_int8_mode_requires_calibration(tiny_setup):
    # ValueError (not assert): the precondition must hold under python -O
    cfg, params, _ = tiny_setup
    with pytest.raises(ValueError, match="calibrator"):
        VisionServer(cfg, params, qparams=vit.quantize_vit(params),
                     calibrator=None,
                     serve_cfg=ServeConfig(mode="int8"))


def test_serve_config_validates():
    with pytest.raises(ValueError, match="mode"):
        ServeConfig(mode="bf16")
    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=())
    with pytest.raises(ValueError, match="buckets"):
        ServeConfig(buckets=(0, 2))
    assert ServeConfig(buckets=[1, "2"]).buckets == (1, 2)  # normalized


def test_deprecated_kwargs_shim(tiny_setup):
    """The pre-ServeConfig keyword surface still works for one release —
    folded into a ServeConfig with a DeprecationWarning — and mixing the
    two construction paths is rejected."""
    cfg, params, images = tiny_setup
    with pytest.warns(DeprecationWarning, match="serve_cfg"):
        server = VisionServer(cfg, params, mode="float", buckets=(1, 2))
    assert server.serve_cfg == ServeConfig(mode="float", buckets=(1, 2))
    server.submit_many(images[:2])
    assert server.run()["requests"] == 2
    with pytest.raises(ValueError, match="not both"):
        VisionServer(cfg, params, serve_cfg=ServeConfig(),
                     buckets=(1,))


def test_make_server_factory():
    """`make_server` is the one-call construction path: registry config
    resolution (including head-mask override), param init, and — for
    int8 — quantization + synthetic-bank calibration, all driven by the
    ServeConfig's build fields."""
    from repro.launch.vision_serve import make_server
    server = make_server("vit_edge", ServeConfig(buckets=(1, 2)))
    images = np.random.default_rng(5).standard_normal(
        (3, server.cfg.image, server.cfg.image, 3)).astype(np.float32)
    server.submit_many(images)
    assert server.run()["requests"] == 3

    q = make_server("vit_edge",
                    ServeConfig(mode="int8", buckets=(2,), calib_images=4))
    assert q.qparams is not None and q.calibrator is not None
    q.submit_many(images[:2])
    assert q.run()["requests"] == 2

    masked = make_server(
        "vit_edge", ServeConfig(buckets=(1,),
                                head_mask=((1, 0, 1, 0),) * 4))
    assert masked.cfg.head_mask == ((1, 0, 1, 0),) * 4
    masked.submit(images[0])
    assert masked.run()["requests"] == 1


@pytest.mark.parametrize("name", vision_registry.list_models())
def test_server_roundtrip_every_registered_model(name):
    """Each registered model (ViT/DeiT/Swin) serves float requests through
    the same VisionServer with nothing model-specific at the call site."""
    cfg = vision_registry.build_cfg(name)
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    images = np.random.default_rng(1).standard_normal(
        (3, cfg.image, cfg.image, 3)).astype(np.float32)
    server = VisionServer(cfg, params,
                          serve_cfg=ServeConfig(buckets=(1, 2)))
    reqs = server.submit_many(images)
    stats = server.run()
    assert stats["requests"] == 3
    for r in reqs:
        assert r.t_done is not None and 0 <= r.pred < cfg.n_classes
        assert np.isfinite(r.logits).all()


def test_server_int8_roundtrip_swin():
    """Swin through the served int8 PTQ path: calibrate, freeze, drain."""
    cfg = vision_registry.build_cfg("swin_t")
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vision_registry.quantize(params)
    images = np.random.default_rng(2).standard_normal(
        (4, cfg.image, cfg.image, 3)).astype(np.float32)
    cal = calibrate(qparams, cfg, images[:2], n_batches=1)
    out = {}
    for mode in ("float", "int8"):
        server = VisionServer(
            cfg, params, qparams=qparams, calibrator=cal,
            serve_cfg=ServeConfig(mode=mode, buckets=(4,)))
        server.submit_many(images)
        server.run()
        out[mode] = np.stack([r.logits for r in server.done])
    scale = np.abs(out["float"]).max()
    err = np.abs(out["float"] - out["int8"]).max()
    assert err <= ptq_tolerance(scale), (err, scale)


def test_server_int8_roundtrip_tnt():
    """TNT through the served int8 PTQ path: both streams quantized,
    calibrated, frozen, drained through the same VisionServer."""
    cfg = vision_registry.build_cfg("tnt_s")
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    qparams = vision_registry.quantize(params)
    images = np.random.default_rng(3).standard_normal(
        (4, cfg.image, cfg.image, 3)).astype(np.float32)
    cal = calibrate(qparams, cfg, images[:2], n_batches=1)
    out = {}
    for mode in ("float", "int8"):
        server = VisionServer(
            cfg, params, qparams=qparams, calibrator=cal,
            serve_cfg=ServeConfig(mode=mode, buckets=(4,)))
        server.submit_many(images)
        server.run()
        out[mode] = np.stack([r.logits for r in server.done])
    scale = np.abs(out["float"]).max()
    err = np.abs(out["float"] - out["int8"]).max()
    assert err <= ptq_tolerance(scale), (err, scale)


def test_pallas_and_xla_backends_agree(tiny_setup):
    cfg, params, images = tiny_setup
    import dataclasses
    logits = {}
    for backend in ("xla", "pallas"):
        bcfg = dataclasses.replace(cfg, backend=backend)
        server = VisionServer(bcfg, params, serve_cfg=ServeConfig(buckets=(4,)))
        server.submit_many(images[:4])
        server.run()
        logits[backend] = np.stack([r.logits for r in server.done])
    np.testing.assert_allclose(logits["pallas"], logits["xla"],
                               rtol=2e-4, atol=2e-4)


def test_compile_bucket_ahead_of_traffic(tiny_setup):
    """A bucket compiled ahead of its first request serves the same
    logits as one compiled on first dispatch; unknown buckets raise."""
    cfg, params, images = tiny_setup
    warm = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(1, 4)))
    compiled = warm.compile_bucket(4)
    assert "dot" in compiled.as_text()
    with pytest.raises(ValueError, match="buckets"):
        warm.compile_bucket(3)
    cold = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(1, 4)))
    for server in (warm, cold):
        server.submit_many(images[:4])
        server.run()
    np.testing.assert_array_equal(
        np.stack([r.logits for r in warm.done]),
        np.stack([r.logits for r in cold.done]))


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_fixed(monkeypatch, tmp_path, env_dir):
    """The compile cache is $JAX_COMPILATION_CACHE_DIR when set (JAX reads
    it; nothing else is configured), else .jax_cache/ at the checkout."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT_ROOT / ".jax_cache")
        assert (compile_cache.CHECKOUT_ROOT / "chip_smoke.py").exists()
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
    else:
        path = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
        assert compile_cache.enable_compile_cache() == path
        assert calls == []


def _requests(images):
    return [VisionRequest(i, im) for i, im in enumerate(images)]


@pytest.mark.parametrize("counts", [(4, 3, 1, 2), (2, 4, 1, 4, 3)])
def test_pooled_stack_matches_a_fresh_stack(tiny_setup, counts):
    """Consecutive dispatches of different request counts into one bucket
    share one host buffer, and each one's logits equal, bit for bit, the
    same program's on a fresh `np.stack` padded with zeros; a padded batch
    after a fuller one finds its padding rows zeroed."""
    cfg, params, images = tiny_setup
    server = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(4,)))
    forward = server.compile_bucket(4)
    buffers = set()
    start = 0
    for k in counts:
        batch = images[start:start + k]
        start = (start + k) % (len(images) - 4)
        inflight = server.dispatch(_requests(batch))
        buffers.add(id(inflight.images))
        assert not inflight.images[k:].any()
        server.complete(inflight)
        fresh = np.concatenate([np.stack(list(batch)),
                                np.zeros((4 - k,) + batch.shape[1:],
                                         batch.dtype)])
        want = np.asarray(forward(server.served_params, jnp.asarray(fresh)))
        np.testing.assert_array_equal(
            np.stack([r.logits for r in inflight.requests]), want[:k])
    assert len(buffers) == 1
    assert server.n_stack_allocated == 1
    assert server.n_stack_reused == len(counts) - 1
    assert server.n_padded == sum(4 - k for k in counts)


def test_stack_buffer_returns_only_on_complete(tiny_setup):
    """Buffers of micro-batches in flight are never shared; completing
    one returns its buffer, once, and the next dispatch reuses it."""
    cfg, params, images = tiny_setup
    server = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(2,)))
    server.submit_many(np.concatenate([images, images])[:14])
    ring = [server.dispatch() for _ in range(3)]
    assert len({id(f.images) for f in ring}) == 3
    assert (server.n_stack_allocated, server.n_stack_reused) == (3, 0)
    buffers = [f.images for f in ring]
    for inflight in ring:
        server.complete(inflight)
        assert inflight.images is None
    server.complete(ring[0])        # a second complete hands back nothing
    nxt = server.dispatch()
    assert any(nxt.images is b for b in buffers)
    assert (server.n_stack_allocated, server.n_stack_reused) == (3, 1)
    ring = [nxt] + [server.dispatch() for _ in range(3)]
    assert len({id(f.images) for f in ring}) == 4
    assert (server.n_stack_allocated, server.n_stack_reused) == (4, 3)
    for inflight in ring:
        server.complete(inflight)
    assert not server.queue


@pytest.mark.parametrize("odd", ["dtype", "shape", "layout"])
def test_stack_buffer_per_image_shape_and_dtype(tiny_setup, odd):
    """Images of another dtype, shape or memory layout in the same bucket
    are stacked in a buffer of their own, laid out as they are, so the
    bucket's float32 C-ordered buffer stays free."""
    cfg, params, images = tiny_setup
    server = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(2,)))
    first = server.dispatch(_requests(images[:2]))
    buf = first.images
    server.complete(first)
    if odd == "dtype":
        other = server.dispatch(_requests(images[2:4].astype(np.float64)))
        assert other.images is not buf
        assert other.images.dtype == np.float64
        server.complete(other)
    elif odd == "layout":        # channels outermost in memory
        planar = np.ascontiguousarray(images[2:4].transpose(0, 3, 1, 2))
        other = server.dispatch(_requests(planar.transpose(0, 2, 3, 1)))
        assert other.images is not buf
        assert other.images[0].strides == planar[0].transpose(1, 2, 0).strides
        server.complete(other)
        want = server.compile_bucket(2)(server.served_params,
                                        jnp.asarray(images[2:4]))
        np.testing.assert_array_equal(
            np.stack([r.logits for r in other.requests]), np.asarray(want))
    else:
        with pytest.raises((TypeError, ValueError)):   # the model refuses it
            server.dispatch(_requests(
                np.zeros((2, cfg.image + 8, cfg.image, 3), np.float32)))
    assert (server.n_stack_allocated, server.n_stack_reused) == (2, 0)
    again = server.dispatch(_requests(images[4:6]))
    assert again.images is buf
    assert (server.n_stack_allocated, server.n_stack_reused) == (2, 1)
    server.complete(again)


def test_stats_report_windowed_kernel_steps_per_bucket():
    """Each compiled bucket's forward counts the windowed kernel's grid
    steps as jit traces it: per stage, (B·nW / Wb) × H a layer; a model
    with no windowed layer counts none."""
    from repro.kernels.vita_layer import window_grid_steps
    cfg = vision_registry.build_cfg("swin_t", backend="pallas")
    params = vision_registry.init_params(jax.random.PRNGKey(0), cfg)
    server = VisionServer(cfg, params, serve_cfg=ServeConfig(buckets=(1, 2)))
    for bucket in server.buckets:
        server.compile_bucket(bucket)

    def expect(bucket):
        total, grid = 0, cfg.image // cfg.patch
        for s, (depth, heads) in enumerate(zip(cfg.depths, cfg.heads)):
            side, d = grid >> s, cfg.embed_dim << s
            n_w = (side // cfg.window) ** 2
            total += depth * window_grid_steps(
                bucket * n_w, n_w, cfg.window ** 2, d, heads,
                int(d * cfg.mlp_ratio))
        return total

    assert server.run()["window_steps"] == {"1": expect(1), "2": expect(2)}
    assert expect(2) < 2 * expect(1)        # the block grows with the fold
    vit_cfg = vision_registry.build_cfg("vit_edge", backend="pallas")
    vit_server = VisionServer(
        vit_cfg, vision_registry.init_params(jax.random.PRNGKey(0), vit_cfg),
        serve_cfg=ServeConfig(buckets=(1,)))
    vit_server.compile_bucket(1)
    assert vit_server.run()["window_steps"] == {"1": 0}
