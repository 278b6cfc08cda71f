"""Chunked RG-LRU linear-recurrence Pallas kernel.

The RG-LRU h_t = a_t * h_{t-1} + b_t is the hot loop of RecurrentGemma's
recurrent mixer.  TPU-native structure (ViTA's streaming philosophy applied
to a recurrence):

  * grid = (batch, T/chunk) with the time dimension ``arbitrary``
    (sequential) — the hidden state h carries across grid steps in a VMEM
    scratch, exactly like ViTA carries layer activations on-chip;
  * within a chunk, the recurrence is evaluated by a log-depth Blelloch
    pass over VMEM-resident tiles (no HBM round-trip for intermediate h);
  * chunk tiles of (a, b) stream HBM->VMEM with the usual double-buffered
    pipeline (the weight-column ping-pong analogue).

Oracle: kernels/ref.rglru_ref (sequential scan).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chunk_scan(a: jax.Array, b: jax.Array, h0: jax.Array):
    """In-VMEM log-depth scan: h_t = a_t h_{t-1} + b_t over chunk rows.
    a, b: (C, W); h0: (W,).  Returns (h_all (C, W), h_last (W,))."""
    c = a.shape[0]
    # fold h0 into the first step
    b = b.at[0].add(a[0] * h0)
    log2 = max(c - 1, 1).bit_length()
    av, bv = a, b
    offset = 1
    for _ in range(log2):
        a_sh = jnp.roll(av, offset, axis=0)
        b_sh = jnp.roll(bv, offset, axis=0)
        idx = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
        valid = idx >= offset
        av_new = jnp.where(valid, av * a_sh, av)
        bv_new = jnp.where(valid, bv + av * b_sh, bv)
        av, bv = av_new, bv_new
        offset *= 2
    return bv, bv[-1]


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, n_chunks: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    h_all, h_last = _chunk_scan(a, b, h_ref[...])
    o_ref[0] = h_all.astype(o_ref.dtype)
    h_ref[...] = h_last


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru_scan(a: jax.Array, b: jax.Array, *, chunk: int = 256,
               interpret: bool = False) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t along axis 1.  a, b: (B, T, W)."""
    bsz, t, w = a.shape
    ch = min(chunk, t)
    while t % ch:
        ch -= 1
    n_chunks = t // ch
    kernel = functools.partial(_rglru_kernel, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=(bsz, n_chunks),
        in_specs=[
            pl.BlockSpec((1, ch, w), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ch, w), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, ch, w), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, t, w), a.dtype),
        scratch_shapes=[pltpu.VMEM((w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
