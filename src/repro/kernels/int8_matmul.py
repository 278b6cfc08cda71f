"""int8 x int8 -> int32 matmul Pallas kernel with fused requantization.

ViTA performs all GEMMs in int8 with int32 accumulation and rescales the
accumulator back to int8/float in dedicated requant units (Sec. III-A).  On
TPU the MXU natively supports int8 x int8 -> int32; this kernel tiles the
(m, k) x (k, n) product over a 3D grid and fuses the per-output-channel
rescale (x_scale * w_scale[n]) into the final k-step — the requant never
round-trips through HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_mm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                    n_kblocks: int, scaled: bool):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kb == n_kblocks - 1)
    def _store():
        acc = acc_ref[...]
        if scaled:
            s = xs_ref[0].astype(jnp.float32) * ws_ref[...].astype(jnp.float32)
            o_ref[...] = (acc.astype(jnp.float32) * s[None, :]).astype(
                o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret"))
def int8_matmul(x_q: jax.Array, w_q: jax.Array,
                x_scale: Optional[jax.Array] = None,
                w_scale: Optional[jax.Array] = None,
                *, block_m: int = 256, block_n: int = 256,
                block_k: int = 512, out_dtype=None,
                interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8.

    Without scales returns int32; with (x_scale scalar, w_scale (N,))
    returns the rescaled float (``out_dtype``, default float32).
    """
    m, k = x_q.shape
    _, n = w_q.shape
    scaled = x_scale is not None or w_scale is not None
    if scaled:
        x_scale = jnp.asarray(x_scale if x_scale is not None else 1.0,
                              jnp.float32).reshape(1)
        if w_scale is None:
            w_scale = jnp.ones((n,), jnp.float32)
        w_scale = w_scale.reshape(n).astype(jnp.float32)
        out_dtype = out_dtype or jnp.float32
    else:
        out_dtype = out_dtype or jnp.int32

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_kblocks = k // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
        pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
    ]
    args = [x_q, w_q]
    if scaled:
        in_specs.append(pl.BlockSpec((1,), lambda i, j, kb: (0,)))
        in_specs.append(pl.BlockSpec((bn,), lambda i, j, kb: (j,)))
        args.extend([x_scale, w_scale])

    def kernel(*refs):
        if scaled:
            x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref = refs
        else:
            x_ref, w_ref, o_ref, acc_ref = refs
            xs_ref = ws_ref = None
        _int8_mm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref,
                        n_kblocks=n_kblocks, scaled=scaled)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_kblocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
