#!/usr/bin/env python3
"""Chip smoke test: serve DeiT-Ti at full width on a TPU through the
Pallas kernels.

Drives the serving path a user calls — `make_server` + `ServeConfig`,
then `VisionServer.submit_many` / `dispatch` / `complete` — for
``deit_t`` at its registry ``full`` geometry (DeiT-Ti/16: 224 px, 12
layers, dim 192, 3 heads), with random weights and images made from
``--seed``.  It serves the images in float and in int8 with
``backend="pallas"``, then again with ``backend="xla"``, and fails
(non-zero exit, no result line) unless:

  * JAX's first device is a TPU — there is no CPU fallback;
  * every Pallas bucket's compiled program holds ``tpu_custom_call``,
    i.e. the kernels were compiled, not interpreted or replaced;
  * Pallas float logits match the xla reference (run at ``highest``
    matmul precision) within ``FLOAT_REL_TOL`` of the logit scale;
  * int8 logits match float within `ptq_tolerance`, the rule the tests
    use, and Pallas int8 matches xla int8 within the same rule.

``--four-chips`` runs only the multi-chip paths and the one-chip logits
they are compared with: ``deit_t`` on a 4x1 ``("data",)`` mesh with
the Pallas backend, and on a 2x2 ``("data", "model")`` mesh with the
xla backend (the Pallas kernels hold no model-axis collectives).

Every line before the last is smoke output, not a benchmark number.
The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Run from the checkout root, one process per chip:
  python chip_smoke.py [--seed N] [--four-chips]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.quant import ptq_tolerance  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.vision_serve import ServeConfig, make_server  # noqa: E402

MODEL = "deit_t"
BUCKETS = (1, 8)
N_IMAGES = 17            # two full 8-buckets and one 1-bucket
# Pallas vs xla float logits: max|diff| <= FLOAT_REL_TOL * max|logits|.
# The xla reference multiplies in full f32; the kernels' f32 dots take
# fewer MXU passes (5.4e-3 of the scale measured on a TPU v5e).
FLOAT_REL_TOL = 2e-2
# A mesh vs one chip, same backend: max|diff| <= MESH_REL_TOL * scale.
MESH_REL_TOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def serve(mode: str, backend: str, images: np.ndarray, *, seed: int,
          kernels: bool, tag: str = "", data_parallel=None, mesh_shape=None,
          **prepared):
    """Build one server through `make_server` (``prepared``: params,
    qparams, calibrator to reuse), compile its buckets ahead of traffic,
    serve ``images`` twice (the second pass is timed) and return (server,
    logits).  ``kernels``: every bucket's program must hold a compiled
    Pallas kernel."""
    sc = ServeConfig(mode=mode, buckets=BUCKETS, full=True, backend=backend,
                     seed=seed, data_parallel=data_parallel,
                     mesh_shape=mesh_shape)
    t0 = time.perf_counter()
    server = make_server(MODEL, sc, **prepared)
    name = f"{tag}{mode}/{backend}"
    say(f"{name}: server ready in {time.perf_counter() - t0:.2f} s "
        f"(mesh {server.mesh_shape}, buckets {server.buckets})")
    for bucket in server.buckets:
        t0 = time.perf_counter()
        text = server.compile_bucket(bucket).as_text()
        n_kernels = text.count("tpu_custom_call")
        say(f"{name}: bucket {bucket} compiled in "
            f"{time.perf_counter() - t0:.2f} s, "
            f"{n_kernels} tpu_custom_call sites")
        if kernels:
            check(n_kernels > 0, f"{name}: bucket {bucket}'s compiled "
                  f"program runs no Pallas kernel")
    logits = None
    for label in ("first pass", "timed pass"):
        reqs = server.submit_many(images)
        t0 = time.perf_counter()
        inflight = []
        while server.queue:
            inflight.append(server.dispatch())
        devices = {d for f in inflight for d in f.out.sharding.device_set}
        for f in inflight:
            server.complete(f)
        wall = time.perf_counter() - t0
        logits = np.stack([r.logits for r in reqs])
        say(f"{name}: {label}: {len(reqs)} requests in "
            f"{len(inflight)} micro-batches, {wall:.3f} s wall, "
            f"outputs on {len(devices)} device(s)")
    check(bool(np.isfinite(logits).all()), f"{name}: non-finite logits")
    check(logits.shape == (len(images), server.cfg.n_classes),
          f"{name}: logits shape {logits.shape}")
    return server, logits


def compare(what: str, got: np.ndarray, want: np.ndarray,
            tol: float) -> None:
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say(f"{what}: max|logit err| {err:.3e} (tolerance {tol:.3e}, "
        f"logit scale {scale:.3e}), argmax agreement {agree:.3f}")
    check(err <= tol, f"{what}: logit error {err:.3e} exceeds {tol:.3e}")


def one_chip(images: np.ndarray, seed: int) -> None:
    f_pl, float_pl = serve("float", "pallas", images, seed=seed,
                           kernels=True)
    params = f_pl.params
    q_pl, int8_pl = serve("int8", "pallas", images, seed=seed,
                          kernels=True, params=params)
    with jax.default_matmul_precision("highest"):
        _, float_xla = serve("float", "xla", images, seed=seed,
                             kernels=False, params=params)
        _, int8_xla = serve("int8", "xla", images, seed=seed,
                            kernels=False, params=params,
                            qparams=q_pl.qparams,
                            calibrator=q_pl.calibrator)
    scale = float(np.abs(float_xla).max())
    compare("float pallas vs xla", float_pl, float_xla,
            FLOAT_REL_TOL * scale)
    compare("int8 pallas vs float pallas", int8_pl, float_pl,
            ptq_tolerance(float(np.abs(float_pl).max())))
    compare("int8 pallas vs int8 xla", int8_pl, int8_xla,
            ptq_tolerance(scale))


def four_chips(images: np.ndarray, seed: int) -> None:
    check(jax.device_count() >= 4,
          f"--four-chips needs 4 devices, found {jax.device_count()}")

    def spans_four(server, what):
        devices = set(server.mesh.devices.flat)
        check(len(devices) == 4, f"{what} spans {len(devices)} device(s)")

    f_pl, ref_float = serve("float", "pallas", images, seed=seed,
                            kernels=True, tag="1 chip ")
    params = f_pl.params
    q_pl, ref_int8 = serve("int8", "pallas", images, seed=seed,
                           kernels=True, tag="1 chip ", params=params)
    quant = dict(qparams=q_pl.qparams, calibrator=q_pl.calibrator)
    mesh_f, data_float = serve("float", "pallas", images, seed=seed,
                               kernels=True, tag="4x1 mesh ",
                               params=params, data_parallel=4)
    spans_four(mesh_f, "4x1 mesh")
    _, data_int8 = serve("int8", "pallas", images, seed=seed, kernels=True,
                         tag="4x1 mesh ", params=params, data_parallel=4,
                         **quant)
    say("2x2 (data, model) mesh serves with backend='xla': the Pallas "
        "kernels hold no model-axis collectives "
        "(kernels.ops._no_pallas_collectives)")
    with jax.default_matmul_precision("highest"):
        _, ref_xla = serve("float", "xla", images, seed=seed,
                           kernels=False, tag="1 chip ", params=params)
        mesh_x, model_xla = serve("float", "xla", images, seed=seed,
                                  kernels=False, tag="2x2 mesh ",
                                  params=params, mesh_shape="2x2")
    spans_four(mesh_x, "2x2 mesh")
    # Each device runs the same f32 math on its own rows (4x1) or heads
    # and MLP columns (2x2): only accumulation order differs from one
    # chip.  int8 may also flip a requant rounding: the PTQ rule holds it.
    compare("4x1 pallas mesh vs 1 chip, float", data_float, ref_float,
            MESH_REL_TOL * float(np.abs(ref_float).max()))
    compare("4x1 pallas mesh vs 1 chip, int8", data_int8, ref_int8,
            ptq_tolerance(float(np.abs(ref_float).max())))
    compare("2x2 xla mesh vs 1 chip, float", model_xla, ref_xla,
            MESH_REL_TOL * float(np.abs(ref_xla).max()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and images")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh paths and the one-chip "
                         "logits they are compared with")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r}; refusing to fall back",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    say(f"smoke output, not benchmark numbers: device_kind "
        f"{dev.device_kind!r}, {jax.device_count()} device(s), "
        f"jax {jax.__version__}, compile cache {cache}")
    rng = np.random.default_rng(args.seed)
    images = rng.standard_normal((N_IMAGES, 224, 224, 3)).astype(np.float32)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(images, args.seed)
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"all checks passed in {time.perf_counter() - t0:.1f} s; "
        f"{n_cached} entries in the compile cache")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
