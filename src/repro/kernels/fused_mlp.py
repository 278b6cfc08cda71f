"""Fused MLP Pallas kernel — the ViTA inter-layer optimization on TPU.

The paper's key MLP idea (Sec. III-B1, Fig. 3): the (N, M) hidden activation
never exists in off-chip memory.  Hidden values are computed, pushed through
the non-linearity, and *immediately* consumed by the output-layer
accumulation.  On TPU this becomes a single kernel whose grid streams chunks
of the hidden dimension through VMEM:

    for j in range(M // bh):                     # grid dim (arbitrary)
        h   = act(x_tile @ W1[:, j*bh:(j+1)*bh]) # engine-1 analogue
        acc += h @ W2[j*bh:(j+1)*bh, :]          # engine-2 analogue

* The activation tile ``x`` is the *stationary* operand (revisited across j)
  — ViTA's input-stationary dataflow.
* W1/W2 chunks stream HBM->VMEM; the Pallas pipeline double-buffers the next
  chunk during compute — ViTA's two-column BRAM ping-pong.
* ViTA's equal-MACs condition (hidden MACs == output MACs per unit time)
  holds by construction: both contractions are (bn x D x bh)-sized MXU work
  in the same grid step.

Supports the gated (SwiGLU) variant used by the LM architectures and the
squared-ReLU used by Nemotron.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from .ref import act_fn


def _fused_mlp_kernel(x_ref, w1_ref, w2_ref, b1_ref, b2_ref, o_ref,
                      acc_ref, *, activation: str, n_hchunks: int,
                      gated: bool, w_gate_ref=None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    h = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    if b1_ref is not None:
        h = h + b1_ref[...].astype(jnp.float32)
    if gated:
        g = jnp.dot(x, w_gate_ref[...], preferred_element_type=jnp.float32)
        h = act_fn(activation)(g) * h
    else:
        h = act_fn(activation)(h)
    # Immediate consumption: the hidden chunk h never leaves VMEM.
    acc_ref[...] += jnp.dot(h.astype(x.dtype), w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_hchunks - 1)
    def _store():
        out = acc_ref[...]
        if b2_ref is not None:
            out = out + b2_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_n", "block_h", "interpret"))
def fused_mlp(x: jax.Array, w1: jax.Array, w2: jax.Array,
              b1: Optional[jax.Array] = None,
              b2: Optional[jax.Array] = None,
              w_gate: Optional[jax.Array] = None,
              *, activation: str = "gelu",
              block_n: int = 256, block_h: int = 512,
              interpret: bool = False) -> jax.Array:
    """out = act-MLP(x) with the hidden layer never materialized.

    x: (..., N, D); w1[, w_gate]: (D, M); w2: (M, D_out).
    block_n: token-tile rows; block_h: hidden-chunk width (VMEM budget:
    bn*D + 2*D*bh + bh*D_out + bn*D_out elements).
    """
    orig_shape = x.shape
    d = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= s
    x2 = x.reshape(n, d)
    m = w1.shape[1]
    d_out = w2.shape[1]
    bn = min(block_n, n)
    bh = min(block_h, m)
    assert n % bn == 0, (n, bn)
    assert m % bh == 0, (m, bh)
    n_hchunks = m // bh
    gated = w_gate is not None

    in_specs = [
        pl.BlockSpec((bn, d), lambda i, j: (i, 0)),        # x: stationary
        pl.BlockSpec((d, bh), lambda i, j: (0, j)),        # w1: streams
        pl.BlockSpec((bh, d_out), lambda i, j: (j, 0)),    # w2: streams
    ]
    args = [x2, w1, w2]
    if b1 is not None:
        in_specs.append(pl.BlockSpec((bh,), lambda i, j: (j,)))
        args.append(b1)
    if b2 is not None:
        in_specs.append(pl.BlockSpec((d_out,), lambda i, j: (0,)))
        args.append(b2)
    if gated:
        in_specs.append(pl.BlockSpec((d, bh), lambda i, j: (0, j)))
        args.append(w_gate)

    kernel = functools.partial(
        _kernel_dispatch, activation=activation, n_hchunks=n_hchunks,
        gated=gated, has_b1=b1 is not None, has_b2=b2 is not None)

    out = pl.pallas_call(
        kernel,
        grid=(n // bn, n_hchunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, d_out), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d_out), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d_out), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out.reshape(*orig_shape[:-1], d_out)


def _kernel_dispatch(x_ref, w1_ref, w2_ref, *rest, activation, n_hchunks,
                     gated, has_b1, has_b2):
    """Unpacks the optional-operand calling convention."""
    refs = list(rest)
    acc_ref = refs.pop()   # scratch is last
    o_ref = refs.pop()     # output before scratch
    it = iter(refs)
    b1_ref = next(it) if has_b1 else None
    b2_ref = next(it) if has_b2 else None
    wg_ref = next(it) if gated else None
    _fused_mlp_kernel(x_ref, w1_ref, w2_ref, b1_ref, b2_ref, o_ref, acc_ref,
                      activation=activation, n_hchunks=n_hchunks,
                      gated=gated, w_gate_ref=wg_ref)
