#!/usr/bin/env python3
"""Time the windowed `vita_layer` kernel on a TPU at each of Swin-T's four
stage shapes, for several block sizes Wb (windows a grid step).

For each stage (49-token windows; D 96, 192, 384, 768; heads 3, 6, 12,
24; MLP 4·D; 64, 16, 4 and 1 windows an image) and each ``--blocks``
value that the stage admits (Wb divides B·nW and lines up with nW), the
kernel runs ``--iters`` times back to back on random float32 operands;
the time of one call is the host clock over those calls, ended by
`block_until_ready`, divided by their number.  ``rule`` marks the Wb
that `window_block` picks.  A stage's milliseconds a bucket are one
call's times its layers (depths 2, 2, 6, 2).

Run from the checkout root on a TPU (any other first device: exit 3)::

    PYTHONPATH=src python benchmarks/window_block_sweep.py --bucket 32

The last line is one JSON object with every (stage, Wb) time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels.vita_layer import _vita_layer_windowed, window_block

# (tokens n, width D, heads H, MLP hidden M, windows an image nW, layers)
STAGES = ((49, 96, 3, 384, 64, 2), (49, 192, 6, 768, 16, 2),
          (49, 384, 12, 1536, 4, 6), (49, 768, 24, 3072, 1, 2))


def _operands(key, bw, n, d, h, m, n_w):
    dh = d // h
    ks = jax.random.split(key, 9)
    r = lambda k, s, sc: jax.random.normal(k, s, jnp.float32) * sc  # noqa
    return (r(ks[0], (bw, n, d), 0.3), r(ks[1], (h, d, dh), 0.05),
            r(ks[2], (h, d, dh), 0.05), r(ks[3], (h, d, dh), 0.05),
            r(ks[4], (h, dh, d), 0.05), jnp.ones(d), jnp.zeros(d),
            jnp.ones(d), jnp.zeros(d), r(ks[5], (d, m), 0.05),
            r(ks[6], (m,), 0.05), r(ks[7], (m, d), 0.05), jnp.zeros(d),
            r(ks[8], (h, n, n), 1.0), jnp.zeros((n_w, n, n)))


def time_call(fn, args, iters: int) -> float:
    """Milliseconds a call, over ``iters`` calls queued back to back."""
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bucket", type=int, default=32)
    ap.add_argument("--blocks", default="1,4,8,16",
                    help="comma-separated Wb values to time")
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: first device is {dev.platform}, not a TPU",
              file=sys.stderr)
        return 3
    rows = []
    for s, (n, d, h, m, n_w, layers) in enumerate(STAGES):
        bw = args.bucket * n_w
        ops = _operands(jax.random.PRNGKey(s), bw, n, d, h, m, n_w)
        rule = window_block(bw, n_w, n, d, m)
        for wb in sorted({int(w) for w in args.blocks.split(",")} | {rule}):
            if bw % wb or (n_w % wb and wb % n_w):
                continue
            fn = jax.jit(lambda *a, wb=wb: _vita_layer_windowed(
                *a, interpret=False, wb=wb))
            try:
                ms = time_call(fn, ops, args.iters)
            except Exception as e:  # noqa: BLE001 - a refused block size
                print(f"stage {s} Wb {wb}: {type(e).__name__}: "
                      f"{str(e)[:200]}")
                continue
            row = {"stage": s, "d": d, "windows": bw, "wb": wb,
                   "rule": wb == rule, "steps": bw // wb * h,
                   "ms_per_call": ms, "ms_per_bucket": ms * layers}
            rows.append(row)
            print(f"stage {s} D {d} Wb {wb:3d}{' rule' if row['rule'] else ''}"
                  f": {ms:.4f} ms a call, {ms * layers:.3f} ms a bucket, "
                  f"{row['steps']} steps", flush=True)
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "bucket": args.bucket, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
