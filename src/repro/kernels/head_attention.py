"""Head-streamed attention Pallas kernels — ViTA's MSA pipeline on TPU.

ViTA (Sec. III-B2, Fig. 4) computes MSA one head at a time so only a single
head's intermediates are staged on-chip, with a row-granular
PE4 -> Softmax -> PE5 pipeline inside the head.  The TPU-native analogue:

  * the kernel grid iterates (batch, head, q-block) — exactly one head's
    working set lives in VMEM per step, and Pallas double-buffers the next
    grid step's K/V blocks during compute (the BRAM ping-pong analogue);
  * inside a head, the N x N score matrix is never materialized — the
    online-softmax recurrence over K/V row-blocks is the row-granular
    pipeline (score row -> softmax -> weighted-V accumulate, streamed).

Supports GQA (Hq % Hkv == 0), causal masking, sliding windows (SWA), and a
separate single-query decode kernel (`decode_attention`) for the serve path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, n_kblocks: int, q_offset: int):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(2)
    q_start = qb * block_q + q_offset
    k_start = kb * block_k

    q = q_ref[0, 0, ...]                   # (bq, dh)
    k = k_ref[0, 0, ...]                   # (bk, dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq,bk)

    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                    jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0, ...],
                            preferred_element_type=jnp.float32))
    m_ref[...] = m_cur

    @pl.when(kb == n_kblocks - 1)
    def _store():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)   # fully-masked rows -> 0
        o_ref[0, 0, ...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "q_offset", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Nq, Dh); k, v: (B, Hkv, Nk, Dh) -> (B, Hq, Nq, Dh)."""
    b, hq, nq, dh = q.shape
    _, hkv, nk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    bq = min(block_q, nq)
    bk = min(block_k, nk)
    assert nq % bq == 0 and nk % bk == 0, (nq, bq, nk, bk)
    n_kblocks = nk // bk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_kblocks=n_kblocks, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq // bq, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h, i, j, g=group: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.reshape(b, hq, nq, dh), k, v)
    return out


# ---------------------------------------------------------------------------
# Decode attention: one new query against a long KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   scale: float, block_k: int, n_kblocks: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, ...]                                 # (g, dh) head group
    k = k_ref[0, 0, ...]                                 # (bk, dh)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (g,bk)
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = kpos < len_ref[0]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[:, None])
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None] +
                    jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0, ...],
                            preferred_element_type=jnp.float32))
    m_ref[...] = m_cur

    @pl.when(kb == n_kblocks - 1)
    def _store():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: Optional[float] = None,
                     block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """Single-token decode attention over a KV cache.

    q: (B, Hq, Dh) — one new query per sequence;
    k_cache, v_cache: (B, Hkv, S, Dh);  lengths: (B,) valid cache lengths.
    Grid iterates (batch, kv-head, kv-block); the Hq/Hkv query-head group for
    one kv head is processed together (g x dh tile).
    """
    b, hq, dh = q.shape
    _, hkv, s_max, _ = k_cache.shape
    group = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    bk = min(block_k, s_max)
    assert s_max % bk == 0
    n_kblocks = s_max // bk

    qg = q.reshape(b, hkv, group, dh)
    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               n_kblocks=n_kblocks)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, dh), lambda b_, h, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h, j: (b_, h, j, 0)),
            pl.BlockSpec((1,), lambda b_, h, j: (b_,)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda b_, h, j: (b_, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k_cache, v_cache, lengths)
    return out.reshape(b, hq, dh)
