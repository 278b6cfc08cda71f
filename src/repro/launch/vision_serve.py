"""VisionServer — micro-batching driver for every registered vision model.

The LM side of `launch/serve.py` does slot-based continuous batching for
autoregressive decode; vision inference is a single forward pass per
request, so the serving shape is different: requests queue up, the server
drains them in micro-batches, pads each micro-batch up to the nearest
*batch bucket* (so only a handful of XLA programs are ever compiled), and
runs the whole bucket through ONE batched forward.

The forward is model-agnostic: any config in `models.vision_registry`
(ViT, DeiT, Swin, TNT) compiles to a `core.schedule` control program
replayed over the shared batched kernels — plain MSA on the
`(batch, head)` Pallas grid, W-MSA on the same grid with windows folded
into the batch axis, TNT inner blocks on the same grid with patches folded
into the batch axis.

Modes:
  * ``float`` — the fp32/bf16 path through the batched Pallas ops;
  * ``int8``  — the PTQ deployment mode of Sec. III-A: per-channel int8
    weights + calibrated activation scales through the fused int8 MSA /
    quantized matmul path.

Multi-device: ``mesh=`` / ``data_parallel=`` shard each drain's batch axis
across a 1-D ``("data",)`` device mesh (params replicated, micro-batch
split — `distributed.sharding.vision_param_specs` / `vision_batch_spec`).
Buckets round up to a multiple of the data-axis size so every padded
micro-batch lands pre-sharded before the one jitted call.
``mesh_shape=`` / ``--mesh DxM`` instead builds the 2-D
``("data", "model")`` latency mesh: the batch still rides ``data`` while
the per-head QKV stacks and MLP columns split over ``model`` with
explicit all-reduces — so a batch=1 request engages every device of the
model axis instead of one.  Either mesh runs the drain under `shard_map`
(`core.schedule.build_sharded_fn`), so each device runs the kernels on
its own shard: GSPMD cannot partition a Pallas kernel.

Fusion is policy-driven per batch bucket: ``--fusion-policy
{always,never,auto}`` (`core.schedule.FusionPolicy`), where ``auto``
consults the measured fused-vs-unfused A/B data in ``--fusion-data`` (the
bench JSON) and fuses only where measurement says it wins; ``--no-fuse``
is shorthand for ``never``.  ``--fuse-group-size N`` additionally
collapses runs of up to N fused layers into one ``layer_group``
megakernel phase (cross-layer weight streaming; under ``auto`` the
grouped variant competes against per-layer fused and unfused on the
measured data).  ``--profile`` runs the per-phase HUE
profiler after each mode's drain (`VisionServer.profile_stats`,
docs/PROFILING.md) and prints the measured-vs-modelled table.

Usage (CPU examples; on the TPU drop the XLA_FLAGS device faking):
  PYTHONPATH=src python -m repro.launch.serve --vision --list-models
  PYTHONPATH=src python -m repro.launch.serve --vision --model swin_t \
      --requests 32 --buckets 1,2,4,8 --mode both
  PYTHONPATH=src python -m repro.launch.serve --vision --model deit_t \
      --fusion-policy auto --profile
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --vision --model vit_edge --devices 8
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.serve --vision --model vit_edge --mesh 4x2
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hue as hue_lib
from repro.core import schedule as sched_lib
from repro.core import spans
from repro.core.quant import Calibrator
from repro.core.schedule import FusionPolicy
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.models import vision_registry, vit


def round_buckets(buckets: Sequence[int], data_parallel: int) -> Tuple[int, ...]:
    """Round each batch bucket up to a multiple of the DATA-axis size (and
    dedupe), so every padded micro-batch divides the mesh's batch axis and
    shards without a replication fallback.

    ``data_parallel`` must be the data-axis size alone, NOT the total
    device count: on a 2-D ``(data, model)`` mesh only ``data`` carries
    the batch, so a (2, 4) mesh rounds buckets to multiples of 2 — padding
    a 2-image bucket to 8 would serve 6 zero images per drain for a mesh
    axis the batch never touches.
    """
    dp = max(int(data_parallel), 1)
    return tuple(sorted({-(-b // dp) * dp for b in buckets}))


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything that shapes HOW a model is served — one frozen value.

    The serving surface grew one keyword at a time (mode, buckets, then
    meshes, then fusion policies, then head masks); this dataclass is the
    single place they all live, so every construction site — the CLI,
    the bench, `tools/hue_report.py`, tests — names the same fields and
    a server can be rebuilt from ``server.serve_cfg`` verbatim.

    Construction paths:
      * ``make_server(name, serve_cfg)`` — resolve the registry config
        (honouring ``full``/``fused``/``fuse_group``/``backend``/
        ``head_mask``), init params, quantize + calibrate for int8, and
        return a ready `VisionServer`;
      * ``VisionServer(cfg, params, serve_cfg=...)`` — bring your own
        config/params (parity tests, shared-params multiplexing); the
        config-build fields (``full``/``fused``/``fuse_group``/
        ``backend``/``head_mask``/``seed``/``calib_images``) are
        make_server's concern and ignored on this path.

    ``head_mask`` overrides the registry config's per-layer head-pruning
    mask (family-shaped: layers x heads rows, per-stage for Swin) — the
    bench's ``--head-sweep`` serves the same model at several surviving-
    head counts this way.
    """

    mode: str = "float"
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    mesh: Optional[Any] = dataclasses.field(default=None, compare=False)
    data_parallel: Optional[int] = None
    mesh_shape: Optional[Any] = None
    fusion_policy: Optional[FusionPolicy] = dataclasses.field(
        default=None, compare=False)
    head_mask: Optional[Any] = None
    # config-build fields (consumed by make_server)
    full: bool = False
    fused: Optional[bool] = None
    fuse_group: Optional[int] = None
    backend: Optional[str] = None
    seed: int = 0
    calib_images: int = 8

    def __post_init__(self):
        if self.mode not in ("float", "int8"):
            raise ValueError(
                f"mode must be 'float' or 'int8', got {self.mode!r}")
        buckets = tuple(int(b) for b in self.buckets)
        if not buckets or min(buckets) <= 0:
            raise ValueError(
                f"batch buckets must be positive, got {self.buckets!r}")
        object.__setattr__(self, "buckets", buckets)


# Ids of dispatched micro-batches, unique across the process's servers.
_BATCH_IDS = itertools.count()


# Sentinel distinguishing "kwarg not passed" from an explicit None on the
# deprecated VisionServer keyword surface (None is a meaningful value for
# most of them).
_UNSET = object()


class VisionRequest:
    """One queued image-classification request.

    Timing is three stamps — ``t_submit`` (queued), ``t_start`` (its
    micro-batch was dispatched) and ``t_done`` (logits materialized) — so
    queue delay and service time are reported SEPARATELY
    (`queue_delay_s` / `service_s`): a warm-up drain inflates only the
    warm-up requests' service time, never a later request's queue
    delay.  ``latency_s`` (the full submit→done span) is kept for
    drain-mode compatibility — every existing stats consumer reads it.
    ``batch`` is the id of the micro-batch that carried the request, the
    argument of its ``serve.*`` spans (`core.spans`).

    ``sla_ms`` is the request's latency budget (None = no deadline);
    the admission layer's SLA-aware bucket selector
    (`launch.admission.select_bucket`) keys off it.
    """

    def __init__(self, rid: int, image: np.ndarray,
                 sla_ms: Optional[float] = None):
        self.rid = rid
        self.image = image
        self.sla_ms = sla_ms
        self.t_submit = time.perf_counter()
        self.t_start: Optional[float] = None
        self.t_done: Optional[float] = None
        self.batch: Optional[int] = None
        self.pred: Optional[int] = None
        self.logits: Optional[np.ndarray] = None

    @property
    def latency_s(self) -> float:
        if self.t_done is None:
            raise RuntimeError(f"request {self.rid} not served yet")
        return self.t_done - self.t_submit

    @property
    def queue_delay_s(self) -> float:
        """Submit → dispatch: time spent waiting in the queue."""
        if self.t_start is None:
            raise RuntimeError(f"request {self.rid} not dispatched yet")
        return self.t_start - self.t_submit

    @property
    def service_s(self) -> float:
        """Dispatch → done: time inside the batched forward."""
        if self.t_start is None:
            raise RuntimeError(f"request {self.rid} not dispatched yet")
        if self.t_done is None:
            raise RuntimeError(f"request {self.rid} not served yet")
        return self.t_done - self.t_start

    def remaining_budget_ms(self, now: Optional[float] = None) -> float:
        """SLA budget left at ``now`` (inf when the request has none)."""
        if self.sla_ms is None:
            return float("inf")
        now = time.perf_counter() if now is None else now
        return self.sla_ms - (now - self.t_submit) * 1e3


class InFlight:
    """One dispatched-but-not-completed micro-batch.

    `VisionServer.dispatch` returns the jitted forward's ASYNC result
    (jax dispatches without blocking), so the caller can assemble and
    dispatch the next micro-batch while this one executes — the
    admission layer's dispatch ring.  `VisionServer.complete` blocks on
    ``out`` and stamps the requests.  ``batch`` is the dispatch's id,
    unique in the process.  ``images`` is the server's host buffer the
    micro-batch was stacked in, ``stack_key`` the pool it belongs to: it
    stays out of the server's pool until `complete` hands it back (an
    `InFlight` never completed keeps it).
    """

    __slots__ = ("requests", "bucket", "out", "t_dispatch", "batch",
                 "images", "stack_key")

    def __init__(self, requests: List[VisionRequest], bucket: int, out,
                 t_dispatch: float, batch: int, images: np.ndarray,
                 stack_key: Tuple):
        self.requests = requests
        self.bucket = bucket
        self.out = out
        self.t_dispatch = t_dispatch
        self.batch = batch
        self.images = images
        self.stack_key = stack_key


class VisionServer:
    """Queue + pad-to-bucket micro-batching over any registered model.

    ``cfg`` may be any config the vision registry understands (ViT/DeiT's
    `ViTConfig`, Swin's `SwinConfig` or TNT's `TNTConfig`); the matching
    schedule-driven forward is resolved per family.  ``buckets`` are the allowed batch
    sizes (ascending).  A drain step takes up to ``buckets[-1]`` queued
    requests, rounds up to the smallest bucket that fits, pads with zero
    images, and runs one batched forward — one compiled program per
    (bucket, mode), cached across the server's life.

    ``mesh`` (a 1-D ``("data",)`` `jax.sharding.Mesh`) or ``data_parallel``
    (device count; builds the mesh via `launch.mesh.make_vision_mesh`)
    turn on data-parallel drains: params/qparams are placed replicated,
    buckets round up to a multiple of the data-axis size, and every padded
    micro-batch is device_put pre-sharded on ``data`` before the one
    jitted call, which runs the replay under `shard_map` — each device
    runs the `(batch, head)` grid kernels on its own rows, fused or
    unfused, float or int8 (the frozen calibration scales are scalars and
    replicate as jit constants).

    ``mesh_shape`` (``"DxM"`` string or ``(data, model)`` tuple) builds
    the 2-D latency mesh instead: drains with a model axis run under
    `shard_map` with the head grid / MLP columns split over ``model``
    (`core.schedule.build_sharded_fn`).  Buckets round to the DATA-axis
    size only, and when the requested buckets include 1 a dedicated
    batch=1 bucket is kept (batch replicated over ``data``, heads still
    split) — the latency fast path.
    """

    def __init__(self, cfg, params, *,
                 serve_cfg: Optional[ServeConfig] = None,
                 qparams=None, calibrator: Optional[Calibrator] = None,
                 model_name: Optional[str] = None,
                 mode=_UNSET, buckets=_UNSET, mesh=_UNSET,
                 data_parallel=_UNSET, mesh_shape=_UNSET,
                 fusion_policy=_UNSET):
        # Deprecated keyword surface (one release): fold stray kwargs into
        # a ServeConfig with a warning; mixing both paths is an error.
        legacy = {k: v for k, v in (("mode", mode), ("buckets", buckets),
                                    ("mesh", mesh),
                                    ("data_parallel", data_parallel),
                                    ("mesh_shape", mesh_shape),
                                    ("fusion_policy", fusion_policy))
                  if v is not _UNSET}
        if legacy:
            if serve_cfg is not None:
                raise ValueError(
                    "pass serve_cfg=ServeConfig(...) OR the deprecated "
                    f"per-field kwargs, not both (got {sorted(legacy)})")
            warnings.warn(
                "VisionServer(mode=/buckets=/mesh=/data_parallel=/"
                "mesh_shape=/fusion_policy=) is deprecated; pass "
                "serve_cfg=ServeConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            serve_cfg = ServeConfig(**legacy)
        sc = serve_cfg if serve_cfg is not None else ServeConfig()
        self.serve_cfg = sc
        mode, buckets = sc.mode, sc.buckets     # validated by ServeConfig
        if mode == "int8":
            if qparams is None:
                raise ValueError("int8 mode needs quantized params")
            if calibrator is None or calibrator.frozen is None:
                raise ValueError("int8 mode needs a frozen "
                                 "activation-scale calibrator")
        mesh = sc.mesh
        if mesh is None and sc.mesh_shape is not None:
            from repro.launch.mesh import make_vision_mesh, parse_mesh_shape
            d, m = parse_mesh_shape(sc.mesh_shape)
            if d * m > 1:
                mesh = make_vision_mesh(data=d, model=m)
        if mesh is None and sc.data_parallel is not None \
                and sc.data_parallel > 1:
            from repro.launch.mesh import make_vision_mesh
            mesh = make_vision_mesh(sc.data_parallel)
        self.mesh = mesh
        # Batch (data) axis size vs model axis size: bucket rounding and
        # batch placement follow ``dp`` alone; ``mp`` decides the
        # shard_map route.  ``n_devices`` is the whole mesh.
        self.dp = int(np.prod([shd.axis_size(mesh, a)
                               for a in shd.dp_axes(mesh)])) if mesh else 1
        self.mp = shd.axis_size(mesh, "model") if mesh else 1
        self.n_devices = int(mesh.devices.size) if mesh is not None else 1
        if mesh is not None:
            # Replicate only the tree this mode's forward closes over —
            # placing the unused one would cost device memory and startup
            # transfer proportional to mesh size for nothing.
            if mode == "int8":
                qparams = shd.shard_vision_params(qparams, mesh)
            else:
                params = shd.shard_vision_params(params, mesh)
        self.cfg = cfg
        self.params = params
        self.qparams = qparams
        self.calibrator = calibrator
        self.mode = mode
        self.model_name = model_name or getattr(cfg, "name", "model")
        self.fusion_policy = sc.fusion_policy
        # Round to the DATA-axis size only (a (2, 4) mesh rounds to 2 —
        # the model axis never carries batch rows).
        self.buckets = round_buckets(buckets, self.dp)
        if self.mp > 1 and 1 in buckets and self.buckets[0] != 1:
            # batch=1 latency fast path: the single image replicates over
            # ``data`` while the model axis still splits the head grid —
            # strictly better than padding the request up to dp images.
            self.buckets = (1,) + self.buckets
        if not self.buckets or self.buckets[0] <= 0:
            raise ValueError(
                f"batch buckets must be positive, got {buckets}")
        # Fused or per-phase schedule, decided per bucket: without a
        # policy every bucket follows ``cfg.fused`` (the pre-policy
        # behaviour); a `FusionPolicy` overrides it from measured
        # (model, mode, batch) A/B data — so a config the bench measured
        # as a fused LOSS serves unfused instead of shipping it silently.
        if sc.fusion_policy is None:
            self._bucket_fused = {b: bool(getattr(cfg, "fused", True))
                                  for b in self.buckets}
            self._bucket_group = {b: int(getattr(cfg, "fuse_group", 1))
                                  for b in self.buckets}
        else:
            self._bucket_fused = sc.fusion_policy.decisions(
                self.model_name, mode, self.buckets)
            self._bucket_group = sc.fusion_policy.group_decisions(
                self.model_name, mode, self.buckets)
        self.queue: List[VisionRequest] = []
        self.done: List[VisionRequest] = []
        self.n_batches = 0
        self.n_padded = 0
        # Host buffers that micro-batches are stacked in, free for reuse,
        # keyed by (bucket, image shape, image strides, dtype);
        # ``n_stack_reused`` counts the dispatches that found one,
        # ``n_stack_allocated`` those that made one (`dispatch`).
        self._stack_free: Dict[Tuple, List[np.ndarray]] = {}
        self.n_stack_reused = 0
        self.n_stack_allocated = 0
        self._rid = 0
        self._forwards: Dict[Tuple, callable] = {}

    @property
    def mesh_shape(self) -> str:
        """``"DxM"`` — data-axis by model-axis size (``"1x1"`` = no mesh).
        The join key bench rows / compare_bench / HUE reports carry."""
        return f"{self.dp}x{self.mp}"

    @property
    def served_params(self):
        """The param tree this server's mode runs on (int8: quantized)."""
        return self.qparams if self.mode == "int8" else self.params

    def _forward_for(self, fused: bool, group: int = 1,
                     bucket: Optional[int] = None):
        """The jitted batched forward ``fn(params, images)`` for one
        (fusion, group-size) variant (built lazily — a policy that never
        flips serves exactly one).  jit's own shape-keyed cache gives one
        compiled program per bucket; the params are its arguments, not
        constants baked into every bucket's program.  On a mesh the
        variant key also carries the bucket's data-divisibility:
        `build_sharded_fn` fixes the batch PartitionSpec (sharded over
        ``data`` vs replicated — the batch=1 fast path) at trace time."""
        group = int(group) if fused else 1
        bucket = int(bucket) if bucket else self.buckets[0]
        div = self.mesh is not None and bucket % self.dp == 0
        key = (fused, group, div)
        fn = self._forwards.get(key)
        if fn is not None:
            return fn
        cfg = dataclasses.replace(self.cfg, fused=fused, fuse_group=group)
        obs = self.calibrator if self.mode == "int8" else None

        def patchify(images):
            # Patchify INSIDE the compiled program: the host-side drain
            # dispatches exactly one XLA call per micro-batch (the reshape
            # fuses into the embed matmul instead of running eagerly).
            return vit.extract_patches(images, cfg.patch)

        if self.mesh is not None:
            # shard_map drain: each device runs the kernels on its batch
            # rows; on a model axis the weights arrive as local head /
            # MLP-column shards and the executor psums at the two
            # residual re-entries.
            sched = vision_registry.make_schedule(cfg)
            fn = jax.jit(sched_lib.build_sharded_fn(
                sched, self.served_params, self.mesh, batch=bucket,
                observer=obs, preprocess=patchify, x_ndim=4))
        else:
            model_fwd = vision_registry.forward_fn(cfg)
            fn = jax.jit(lambda p, images: model_fwd(
                p, patchify(images), cfg, observer=obs))
        self._forwards[key] = fn
        return fn

    def _place(self, images: np.ndarray):
        """Put a padded micro-batch on the device(s).  Buckets are rounded
        to a multiple of the data-axis size, so on a mesh it lands
        pre-sharded (batch on ``data``) before the single jitted call —
        each device receives only its own shard straight from the host."""
        if self.mesh is not None:
            return shd.shard_vision_batch(images, self.mesh)
        return jnp.asarray(images)

    def compile_bucket(self, bucket: int):
        """Compile the forward that serves ``bucket`` ahead of its first
        request and return it as a `jax.stages.Compiled` (its
        ``as_text()`` names the kernels the program runs).  jit keeps the
        executable, so the bucket's first dispatch does not compile
        again."""
        bucket = int(bucket)
        if bucket not in self.buckets:
            raise ValueError(f"{bucket} is not one of this server's "
                             f"buckets {self.buckets}")
        images = self._place(np.zeros(
            (bucket, self.cfg.image, self.cfg.image, 3), np.float32))
        forward = self._forward_for(self._bucket_fused.get(bucket, True),
                                    self._bucket_group.get(bucket, 1),
                                    bucket)
        return forward.lower(self.served_params, images).compile()

    # -- request plane ----------------------------------------------------

    def submit(self, image: np.ndarray) -> VisionRequest:
        req = VisionRequest(self._rid, np.asarray(image))
        self._rid += 1
        self.queue.append(req)
        return req

    def submit_many(self, images: np.ndarray) -> List[VisionRequest]:
        return [self.submit(im) for im in images]

    # -- execution plane --------------------------------------------------

    def _bucket_for(self, k: int) -> int:
        for b in self.buckets:
            if b >= k:
                return b
        return self.buckets[-1]

    def dispatch(self, requests: Optional[List[VisionRequest]] = None,
                 bucket: Optional[int] = None) -> Optional[InFlight]:
        """Assemble one micro-batch and launch the batched forward WITHOUT
        blocking on the result (jax dispatches asynchronously), returning
        an `InFlight` handle for `complete`.

        ``requests`` defaults to popping up to ``buckets[-1]`` from this
        server's own queue (the drain path); the admission layer passes
        its own request group instead (its queues are per model, sorted
        by deadline).  ``bucket`` defaults to the smallest bucket that
        fits — the SLA-aware scheduler overrides it with its measured
        pick.  Each request's ``t_start`` is stamped here, so queue
        delay and service time split at the dispatch boundary.

        The images are stacked into a host buffer the server owns, taken
        from a free list for the (bucket, image shape, image strides,
        dtype) or, when none is free, made by `np.stack` itself, so that
        it is laid out as the images are (an array read back from a
        device need not be C-ordered; copying it into another order
        costs several times a straight copy).  Padding rows are zeroed.
        The buffer rides on the returned `InFlight` and is reused only
        after `complete` of that `InFlight`, when the forward's output
        is ready and so its input transfer is over, whatever the backend
        does with host memory.
        """
        if requests is None:
            if not self.queue:
                return None
            take = min(len(self.queue), self.buckets[-1])
            requests, self.queue = self.queue[:take], self.queue[take:]
        elif not requests:
            return None
        bucket = self._bucket_for(len(requests)) if bucket is None \
            else int(bucket)
        if len(requests) > bucket:
            raise ValueError(
                f"{len(requests)} requests cannot ride a {bucket}-bucket")
        batch = next(_BATCH_IDS)
        on = spans.on()
        if on:
            top = spans.begin("serve.dispatch", batch)
            part = spans.begin("serve.stack", batch)
        try:
            rows = [r.image for r in requests]
            k = len(rows)
            key = (bucket, rows[0].shape, rows[0].strides,
                   np.result_type(*{im.dtype for im in rows}))
            free = self._stack_free.get(key)
            if free:
                images = free.pop()
                np.stack(rows, out=images[:k])
                self.n_stack_reused += 1
            else:
                images = np.stack(
                    rows + [np.zeros_like(rows[0])] * (bucket - k))
                self.n_stack_allocated += 1
            if bucket > k:    # pad with zeros, over an earlier batch's rows
                images[k:] = 0
                self.n_padded += bucket - k
            if on:
                part = spans.switch(part, "serve.place", batch)
            placed = self._place(images)
            if on:
                part = spans.switch(part, "serve.launch", batch)
            forward = self._forward_for(self._bucket_fused.get(bucket, True),
                                        self._bucket_group.get(bucket, 1),
                                        bucket)
            out = forward(self.served_params, placed)  # async: no block
            if on:
                spans.end(part)
            t = time.perf_counter()
            for req in requests:
                req.t_start = t
                req.batch = batch
            self.n_batches += 1
            return InFlight(requests, bucket, out, t, batch, images, key)
        finally:
            if on:
                spans.end(top)

    def complete(self, inflight: Optional[InFlight]) -> int:
        """Block until an in-flight micro-batch's logits materialize and
        stamp its requests done; returns the number of requests served.
        Once the logits are ready the micro-batch's host buffer goes back
        to the server's free list, once, for the next `dispatch` of its
        (bucket, image shape, image strides, dtype)."""
        if inflight is None:
            return 0
        batch = inflight.batch
        on = spans.on()
        if on:
            top = spans.begin("serve.complete", batch)
            part = spans.begin("serve.wait", batch)
        try:
            out = jax.block_until_ready(inflight.out)
            if on:
                part = spans.switch(part, "serve.readback", batch)
            logits = np.asarray(out)
            t = time.perf_counter()
            if on:
                part = spans.switch(part, "serve.stamp", batch)
            images, inflight.images = inflight.images, None
            if images is not None:
                self._stack_free.setdefault(
                    inflight.stack_key, []).append(images)
            for i, req in enumerate(inflight.requests):
                req.t_done = t
                req.logits = logits[i]
                req.pred = int(np.argmax(logits[i]))
            self.done.extend(inflight.requests)
            if on:
                spans.end(part)
            return len(inflight.requests)
        finally:
            if on:
                spans.end(top)

    def step(self) -> int:
        """Drain one micro-batch; returns the number of requests served.
        The blocking compose of `dispatch` + `complete` — the closed-list
        drain path (`run`) uses it unchanged."""
        return self.complete(self.dispatch())

    def profile_stats(self, batch: Optional[int] = None, *,
                      warmup: int = 1, repeats: int = 2) -> Dict:
        """Profile one micro-batch through the per-phase replay and return
        the live HUE report for this server's (model, mode).

        The serving-side entry point to the observability loop: the same
        rows `tools/hue_report.py` renders — per phase kind, measured ms
        (block-until-ready per phase, best of ``repeats`` after
        ``warmup`` compile replays) joined against the analytic
        `perfmodel.expected_phase_cycles` / `expected_phase_macs`
        attribution.  ``batch`` defaults to the smallest bucket; the
        fusion variant profiled is the one this server would actually
        serve that bucket with (policy-decided).  Runs outside the
        drain loop — profiling traffic never perturbs queued requests.
        """
        bucket = int(batch) if batch else self.buckets[0]
        fused = self._bucket_fused.get(bucket)
        if fused is None:
            fused = (self.fusion_policy.decide(self.model_name, self.mode,
                                               bucket)
                     if self.fusion_policy
                     else bool(getattr(self.cfg, "fused", True)))
        group = self._bucket_group.get(bucket)
        if group is None:
            group = (self.fusion_policy.decide_group(
                self.model_name, self.mode, bucket)
                if self.fusion_policy
                else int(getattr(self.cfg, "fuse_group", 1)))
        group = group if fused else 1
        cfg = dataclasses.replace(self.cfg, fused=fused, fuse_group=group)
        sched = vision_registry.make_schedule(cfg)
        params = self.served_params
        if self.mp > 1:
            # The per-phase profiler jits each phase on its own; pulling
            # the model-axis-sharded tree back to host profiles the
            # single-device replay (per-phase attribution, not mesh
            # latency — the drain stats carry that).
            params = jax.device_get(params)
        obs = self.calibrator if self.mode == "int8" else None
        images = jnp.zeros((bucket, cfg.image, cfg.image, 3), jnp.float32)
        patches = vit.extract_patches(images, cfg.patch)
        _, records = sched_lib.profile_schedule(
            sched, params, patches, observer=obs,
            warmup=warmup, repeats=repeats)
        report = hue_lib.live_hue_report(
            vision_registry.make_spec(cfg), records, fused=fused,
            group_size=group)
        report.update({"model": self.model_name, "config": cfg.name,
                       "mode": self.mode, "batch": bucket, "fused": fused,
                       "group_size": group, "devices": self.n_devices,
                       "mesh_shape": self.mesh_shape})
        return report

    def run(self) -> Dict[str, float]:
        """Drain the whole queue and return this run's serving statistics."""
        batches0, padded0, done0 = self.n_batches, self.n_padded, \
            len(self.done)
        t0 = time.perf_counter()
        served = 0
        while self.queue:
            served += self.step()
        dt = time.perf_counter() - t0
        # Slice this run's requests from the pre-run high-water mark: the
        # window is correct by construction for every served count (a
        # ``done[-served:]`` slice is only safe behind a served > 0 guard
        # — at 0 it silently means the whole list).  Schema is identical
        # whether or not anything was served (zeros when idle).
        reqs = self.done[done0:]
        lat_ms = np.array([r.latency_s for r in reqs]) * 1e3 \
            if served else np.zeros((0,))
        queue_ms = np.array([r.queue_delay_s for r in reqs]) * 1e3 \
            if served else np.zeros((0,))
        service_ms = np.array([r.service_s for r in reqs]) * 1e3 \
            if served else np.zeros((0,))
        return {
            "mode": self.mode,
            "requests": served,
            "devices": self.n_devices,
            "mesh_shape": self.mesh_shape,
            "fusion_policy": (self.fusion_policy.mode
                              if self.fusion_policy else None),
            "fused_buckets": {str(b): bool(f)
                              for b, f in sorted(
                                  self._bucket_fused.items())},
            "group_buckets": {str(b): int(g)
                              for b, g in sorted(
                                  self._bucket_group.items())},
            "batches": self.n_batches - batches0,
            "padded": self.n_padded - padded0,
            "wall_s": dt,
            "throughput_img_s": served / dt if dt > 0 else 0.0,
            "latency_p50_ms": float(np.percentile(lat_ms, 50))
            if served else 0.0,
            "latency_p99_ms": float(np.percentile(lat_ms, 99))
            if served else 0.0,
            "latency_mean_ms": float(lat_ms.mean()) if served else 0.0,
            # queue-delay vs service-time split (submit→dispatch and
            # dispatch→done) — the spans latency_p* conflates
            "queue_delay_p50_ms": float(np.percentile(queue_ms, 50))
            if served else 0.0,
            "service_p50_ms": float(np.percentile(service_ms, 50))
            if served else 0.0,
        }


# ---------------------------------------------------------------------------
# Calibration helper + CLI
# ---------------------------------------------------------------------------


def calibrate(qparams, cfg, images: np.ndarray,
              n_batches: int = 4) -> Calibrator:
    """Run calibration forwards and freeze the activation scales.

    Model-agnostic: the forward is resolved from the config's family, so
    Swin calibrates through the same windowed int8 path it serves with.
    """
    fwd = vision_registry.forward_fn(cfg)
    cal = Calibrator()
    for chunk in np.array_split(images, n_batches):
        if len(chunk) == 0:
            continue
        fwd(qparams, vit.extract_patches(
            jnp.asarray(chunk), cfg.patch), cfg, observer=cal)
    cal.freeze()
    return cal


def make_server(cfg_name: str, serve_cfg: Optional[ServeConfig] = None, *,
                params=None, qparams=None,
                calibrator: Optional[Calibrator] = None,
                calib_bank: Optional[np.ndarray] = None) -> VisionServer:
    """Build a ready `VisionServer` for a registered model name.

    The one construction path the CLI, the bench and `tools/hue_report.py`
    share: resolves the registry config through ``serve_cfg``'s build
    fields (``full``/``fused``/``fuse_group``/``backend``/``head_mask``),
    inits params at ``serve_cfg.seed`` when not supplied, and — for int8 —
    quantizes and calibrates (on ``calib_bank`` or ``calib_images``
    synthetic images) unless a frozen calibrator is passed in.

    ``params``/``qparams``/``calibrator`` short-circuit the matching step,
    so callers serving one model under several `ServeConfig`s (the bench's
    mode × placement sweeps) pay init + calibration once.
    """
    sc = serve_cfg if serve_cfg is not None else ServeConfig()
    cfg = vision_registry.build_cfg(
        cfg_name, full=sc.full, backend=sc.backend, fused=sc.fused,
        fuse_group=sc.fuse_group, head_mask=sc.head_mask)
    if params is None:
        params = vision_registry.init_params(
            jax.random.PRNGKey(sc.seed), cfg)
    if sc.mode == "int8":
        if qparams is None:
            qparams = vision_registry.quantize(params)
        if calibrator is None:
            bank = calib_bank
            if bank is None:
                rng = np.random.default_rng(sc.seed)
                bank = rng.standard_normal(
                    (sc.calib_images, cfg.image, cfg.image, 3)
                ).astype(np.float32)
            calibrator = calibrate(qparams, cfg, bank)
    return VisionServer(cfg, params, serve_cfg=sc, qparams=qparams,
                        calibrator=calibrator, model_name=cfg_name)


def build_edge_vit(image: int = 32, patch: int = 8, dim: int = 96,
                   heads: int = 4, layers: int = 4, n_classes: int = 10,
                   backend: Optional[str] = None) -> vit.ViTConfig:
    """Custom edge-ViT builder (the registry's ``vit_edge`` covers the
    default geometry; this remains for tests and ad-hoc configs)."""
    return vit.ViTConfig(name=f"vit_edge_{image}", image=image, patch=patch,
                         dim=dim, heads=heads, layers=layers,
                         n_classes=n_classes, backend=backend)


def serve_model(cfg, *, requests: int, buckets: Sequence[int],
                modes: Sequence[str], seed: int = 0, calib_images: int = 8,
                name: Optional[str] = None, devices: int = 1,
                mesh_shape=None,
                fusion_policy: Optional[FusionPolicy] = None,
                profile: bool = False) -> List[Dict[str, float]]:
    """Init params, (optionally) quantize+calibrate, and drain ``requests``
    random images through a `VisionServer` per mode.  Returns one stats row
    per mode, tagged ``model`` = registry ``name`` (falling back to the
    config name — the same join key the bench JSON uses) and ``config`` =
    the concrete geometry's name.  ``devices`` > 1 shards each drain's
    batch axis across that many devices (calibration stays single-device;
    only the frozen scales reach the sharded path); ``mesh_shape``
    (``"DxM"``) builds the 2-D latency mesh instead and takes precedence.
    ``fusion_policy`` overrides ``cfg.fused`` per bucket; ``profile``
    additionally runs the per-phase HUE profiler after each mode's drain,
    prints the measured-vs-modelled table, and attaches the report to the
    row."""
    params = vision_registry.init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    images = rng.standard_normal(
        (requests, cfg.image, cfg.image, 3)).astype(np.float32)

    qparams = cal = None
    if "int8" in modes:
        qparams = vision_registry.quantize(params)
        cal = calibrate(qparams, cfg, images[:calib_images])

    all_stats = []
    for mode in modes:
        sc = ServeConfig(mode=mode, buckets=tuple(buckets),
                         data_parallel=devices, mesh_shape=mesh_shape,
                         fusion_policy=fusion_policy)
        server = VisionServer(cfg, params, serve_cfg=sc, qparams=qparams,
                              calibrator=cal, model_name=name)
        server.submit_many(images)
        stats = server.run()
        stats["model"] = name or cfg.name
        stats["config"] = cfg.name
        all_stats.append(stats)
        print(f"[vision-serve] {cfg.name} mode={mode} "
              f"mesh={stats['mesh_shape']} devices={stats['devices']} "
              f"{stats['requests']} reqs in {stats['wall_s']:.2f}s -> "
              f"{stats['throughput_img_s']:.1f} img/s, "
              f"p50 {stats['latency_p50_ms']:.1f}ms "
              f"p99 {stats['latency_p99_ms']:.1f}ms "
              f"({stats['batches']} batches, {stats['padded']} padded)")
        if fusion_policy is not None:
            print(f"[vision-serve] fusion policy {fusion_policy.mode}: "
                  f"fused buckets {stats['fused_buckets']} "
                  f"group sizes {stats['group_buckets']}")
        if profile:
            report = server.profile_stats()
            stats["hue_profile"] = report
            print(hue_lib.render_hue_table(
                report,
                title=f"{stats['model']} ({cfg.name}) mode={mode} "
                      f"fused={report['fused']} batch={report['batch']}"))
    return all_stats


def serve_stream(model_names: Sequence[str], *, modes: Sequence[str],
                 buckets: Sequence[int], trace, serving: str = "continuous",
                 seed: int = 0, calib_images: int = 8, devices: int = 1,
                 mesh_shape=None, latency_mesh=None,
                 fusion_policy: Optional[FusionPolicy] = None,
                 bench_data=None, full: bool = False,
                 max_inflight: int = 2) -> List[Dict[str, float]]:
    """Open-stream serving: replay an arrival ``trace``
    (`launch.admission.Arrival` list) through the continuous-batching
    admission layer (``serving="continuous"``) or the fixed-bucket drain
    baseline (``serving="drain"``, single model only).  One
    `VisionServer` per model in ``model_names`` shares the devices;
    SLA bucket tables seed from ``bench_data`` (a bench JSON path/dict
    with measured per-batch latencies) and fall back to a live
    measurement.  ``latency_mesh`` (a ``"DxM"`` shape) additionally
    builds a batch=1 2-D latency-path server per model that
    tight-deadline singles route to.  Returns one stats row per mode."""
    from repro.launch import admission as adm
    rows = []
    for mode in modes:
        servers, lat_servers, banks, tables = {}, {}, {}, {}
        for nm in model_names:
            cfg = vision_registry.build_cfg(nm, full=full)
            params = vision_registry.init_params(
                jax.random.PRNGKey(seed), cfg)
            rng = np.random.default_rng(seed)
            banks[nm] = rng.standard_normal(
                (calib_images, cfg.image, cfg.image, 3)).astype(np.float32)
            qparams = cal = None
            if mode == "int8":
                qparams = vision_registry.quantize(params)
                cal = calibrate(qparams, cfg, banks[nm])
            sc = ServeConfig(mode=mode, buckets=tuple(buckets),
                             data_parallel=devices, mesh_shape=mesh_shape,
                             fusion_policy=fusion_policy)
            servers[nm] = VisionServer(
                cfg, params, serve_cfg=sc, qparams=qparams,
                calibrator=cal, model_name=nm)
            if latency_mesh is not None:
                lat_sc = dataclasses.replace(
                    sc, buckets=(1,), data_parallel=None,
                    mesh_shape=latency_mesh)
                lat_servers[nm] = VisionServer(
                    cfg, params, serve_cfg=lat_sc, qparams=qparams,
                    calibrator=cal, model_name=nm)
            if bench_data is not None:
                table = adm.latency_table_from_bench(bench_data, nm, mode)
                if table:
                    tables[nm] = table
        if serving == "drain":
            if len(servers) != 1:
                raise ValueError("the drain baseline serves a single model")
            (nm, server), = servers.items()
            adm.measure_bucket_latencies(server)       # compile warm-up
            stats = adm.run_drain_stream(server, trace, banks)
            stats["model"] = nm
        else:
            controller = adm.AdmissionController(
                servers, latencies=tables or None,
                latency_servers=lat_servers or None,
                max_inflight=max_inflight)
            stats = adm.run_open_stream(controller, trace, banks)
            stats["model"] = ",".join(model_names)
        stats.update({"mode": mode, "serving": serving,
                      "devices": next(iter(servers.values())).n_devices,
                      "mesh_shape": next(iter(servers.values())).mesh_shape,
                      "offered": len(trace)})
        rows.append(stats)
        print(f"[vision-serve] stream {stats['model']} mode={mode} "
              f"serving={serving} {stats['requests']} reqs in "
              f"{stats['wall_s']:.2f}s -> "
              f"{stats['throughput_img_s']:.1f} img/s sustained, "
              f"p50 {stats['latency_p50_ms']:.1f}ms "
              f"p95 {stats['latency_p95_ms']:.1f}ms "
              f"p99 {stats['latency_p99_ms']:.1f}ms "
              f"(queue p50 {stats['queue_delay_p50_ms']:.1f}ms, "
              f"sla misses {stats['sla_misses']})")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="vision_serve",
        description="Serve a registered vision model (ViT/DeiT/Swin/TNT) "
                    "through the batched ViTA pipeline.")
    ap.add_argument("--model", default="vit_edge",
                    help="registered model to serve (see --list-models); "
                         "open-stream runs (--arrival-rate/--trace) accept "
                         "a comma-separated list, one multiplexed lane "
                         "per model")
    ap.add_argument("--list-models", action="store_true",
                    help="print the registry and exit")
    ap.add_argument("--full", action="store_true",
                    help="use the paper-scale geometry instead of the "
                         "CPU-friendly reduced one")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--buckets", default="1,2,4,8")
    ap.add_argument("--mode", choices=("float", "int8", "both"),
                    default="both")
    ap.add_argument("--backend", choices=("xla", "pallas"), default=None,
                    help="kernel dispatch override (default: config's)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="keep the per-phase schedule (disable the fused "
                         "msa+mlp layer kernels) — for A/B comparison; "
                         "shorthand for --fusion-policy never")
    ap.add_argument("--fusion-policy", choices=FusionPolicy.MODES,
                    default=None,
                    help="fuse/don't-fuse decision per (model, mode, "
                         "batch): 'always' (the default behaviour), "
                         "'never' (per-phase A/B), or 'auto' — consult "
                         "measured A/B data from --fusion-data and fuse "
                         "only where it measured as a win")
    ap.add_argument("--fusion-data",
                    default=os.path.join("results",
                                         "BENCH_vision_serve.json"),
                    help="bench JSON seeding the 'auto' policy's measured "
                         "(model, mode, batch) -> fusion_speedup table")
    ap.add_argument("--fuse-group-size", type=int, default=1,
                    help="layer-group megakernel size: collapse runs of "
                         "up to this many fused layers into one "
                         "layer_group pallas_call (1 = per-layer fused "
                         "chain; groups form only where the schedule "
                         "allows — see docs/MODELS.md)")
    ap.add_argument("--profile", action="store_true",
                    help="after each mode's drain, run the per-phase HUE "
                         "profiler and print the measured-vs-modelled "
                         "table (docs/PROFILING.md)")
    ap.add_argument("--devices", type=int, default=1,
                    help="data-parallel device count: shard each drain's "
                         "batch axis across this many devices (params "
                         "replicated; buckets round up to a multiple)")
    ap.add_argument("--mesh", default=None,
                    help="2-D mesh shape 'DxM' (data x model), e.g. 4x2: "
                         "batch on the data axis, per-head QKV stacks and "
                         "MLP columns split over the model axis under "
                         "shard_map — the batch=1 latency path; takes "
                         "precedence over --devices")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="open-stream serving: Poisson arrival rate in "
                         "requests/s through the continuous-batching "
                         "admission layer (launch/admission.py) instead "
                         "of the closed-list drain")
    ap.add_argument("--sla-ms", type=float, default=None,
                    help="per-request latency budget (ms) for the "
                         "open-stream path: the SLA-aware scheduler "
                         "picks each micro-batch's bucket from measured "
                         "per-batch latencies so the budget holds")
    ap.add_argument("--trace", default=None,
                    help="replay an arrival trace JSON ({'arrivals': "
                         "[{'t': s, 'model'?: name, 'sla_ms'?: ms}]}) "
                         "instead of synthesizing Poisson arrivals; "
                         "entries naming several registered models "
                         "multiplex their per-model queues onto the "
                         "same devices")
    ap.add_argument("--serving", choices=("continuous", "drain"),
                    default="continuous",
                    help="open-stream scheduler: the continuous-batching "
                         "admission layer (default) or the fixed-bucket "
                         "drain baseline it is benched against")
    ap.add_argument("--latency-mesh", default=None,
                    help="open-stream only: additionally build a batch=1 "
                         "2-D (data, model) latency-path server per "
                         "model on this 'DxM' mesh; tight-deadline "
                         "singles route to it")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None,
                    help="write stats as a BENCH_*.json-style record")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.list_models:
        for name in vision_registry.list_models():
            entry = vision_registry.get(name)
            print(f"{name:10s} [{entry.family}] {entry.description}")
        return []

    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.mesh is not None:
        from repro.launch.mesh import parse_mesh_shape
        d, m = parse_mesh_shape(args.mesh)
        if d * m > jax.device_count():
            raise SystemExit(
                f"[vision-serve] --mesh {args.mesh} needs {d * m} devices "
                f"but only {jax.device_count()} visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={d * m}")
    if args.devices > jax.device_count():
        raise SystemExit(
            f"[vision-serve] --devices {args.devices} but only "
            f"{jax.device_count()} visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.devices}")
    if args.no_fuse and args.fusion_policy:
        raise SystemExit("[vision-serve] --no-fuse and --fusion-policy "
                         "conflict; --no-fuse is shorthand for "
                         "--fusion-policy never")
    if args.fuse_group_size < 1:
        raise SystemExit("[vision-serve] --fuse-group-size must be >= 1")
    policy = None
    if args.fusion_policy == "auto":
        if os.path.exists(args.fusion_data):
            policy = FusionPolicy.from_bench(
                args.fusion_data, default_group=args.fuse_group_size)
        else:
            print(f"[vision-serve] WARNING: --fusion-data "
                  f"{args.fusion_data} not found; 'auto' falls back to "
                  f"the modelled default (fuse)")
            policy = FusionPolicy(mode="auto",
                                  default_group=args.fuse_group_size)
    elif args.fusion_policy:
        policy = FusionPolicy(mode=args.fusion_policy,
                              default_group=args.fuse_group_size)
    modes = ("float", "int8") if args.mode == "both" else (args.mode,)
    if args.arrival_rate is not None or args.trace is not None:
        # open-stream serving multiplexes: --model may name several
        # models comma-separated (one lane each, sharing the mesh)
        model_arg = [m for m in args.model.split(",") if m]
        from repro.launch import admission as adm
        if args.trace is not None:
            trace = adm.load_trace(args.trace, model_arg[0], args.sla_ms)
        else:
            if args.arrival_rate <= 0:
                raise SystemExit("[vision-serve] --arrival-rate must be "
                                 "> 0")
            trace = adm.poisson_trace(
                args.arrival_rate, args.requests,
                model_arg if len(model_arg) > 1 else model_arg[0],
                sla_ms=args.sla_ms, seed=args.seed)
        names = sorted({a.model for a in trace})
        unknown = sorted(set(names) - set(vision_registry.list_models()))
        if unknown:
            raise SystemExit(f"[vision-serve] trace names unregistered "
                             f"model(s): {', '.join(unknown)}")
        bench_data = args.fusion_data \
            if os.path.exists(args.fusion_data) else None
        all_stats = serve_stream(
            names, modes=modes, buckets=buckets, trace=trace,
            serving=args.serving, seed=args.seed, devices=args.devices,
            mesh_shape=args.mesh, latency_mesh=args.latency_mesh,
            fusion_policy=policy, bench_data=bench_data, full=args.full)
        if args.json_out:
            os.makedirs(os.path.dirname(args.json_out) or ".",
                        exist_ok=True)
            with open(args.json_out, "w") as f:
                json.dump({"bench": "vision_serve_stream",
                           "models": names, "serving": args.serving,
                           "arrival_rate": args.arrival_rate,
                           "sla_ms": args.sla_ms, "trace": args.trace,
                           "buckets": list(buckets),
                           "device_count": jax.device_count(),
                           "runs": all_stats}, f, indent=2)
            print(f"[vision-serve] wrote {args.json_out}")
        return all_stats
    if args.model not in vision_registry.list_models():
        raise SystemExit(
            f"[vision-serve] unknown model '{args.model}'; registered: "
            f"{', '.join(vision_registry.list_models())} "
            f"(comma-separated lists need --arrival-rate or --trace)")
    cfg = vision_registry.build_cfg(args.model, full=args.full,
                                    backend=args.backend,
                                    fused=not args.no_fuse,
                                    fuse_group=args.fuse_group_size)
    all_stats = serve_model(cfg, requests=args.requests, buckets=buckets,
                            modes=modes, seed=args.seed, name=args.model,
                            devices=args.devices, mesh_shape=args.mesh,
                            fusion_policy=policy,
                            profile=args.profile)

    if args.json_out:
        os.makedirs(os.path.dirname(args.json_out) or ".", exist_ok=True)
        with open(args.json_out, "w") as f:
            json.dump({"bench": "vision_serve", "model": args.model,
                       "config": cfg.name, "buckets": list(buckets),
                       "devices": args.devices, "mesh": args.mesh,
                       "device_count": jax.device_count(),
                       "runs": all_stats}, f, indent=2)
        print(f"[vision-serve] wrote {args.json_out}")
    return all_stats


if __name__ == "__main__":
    main()
