"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.head_attention import decode_attention, flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.vita_layer import vita_layer, vita_layer_int8
from repro.kernels.vita_msa import vita_msa, vita_msa_batched, vita_msa_int8


def rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# fused MLP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,m,bn,bh", [
    (128, 64, 256, 64, 64),
    (256, 128, 512, 128, 256),
    (64, 96, 192, 64, 192),          # non-128-aligned d
])
@pytest.mark.parametrize("act,gated,bias", [
    ("gelu", False, True),
    ("silu", True, False),
    ("relu2", False, False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_mlp(n, d, m, bn, bh, act, gated, bias, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = rand(ks[0], (n, d), dtype, 0.5)
    w1 = rand(ks[1], (d, m), dtype, 0.05)
    w2 = rand(ks[2], (m, d), dtype, 0.05)
    b1 = rand(ks[3], (m,), dtype, 0.1) if bias else None
    b2 = rand(ks[4], (d,), dtype, 0.1) if bias else None
    wg = rand(ks[5], (d, m), dtype, 0.05) if gated else None
    out = fused_mlp(x, w1, w2, b1, b2, wg, activation=act,
                    block_n=bn, block_h=bh, interpret=True)
    expect = ref.fused_mlp_ref(x, w1, b1, w2, b2, activation=act, w_gate=wg)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expect.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 10)


def test_fused_mlp_batched_leading_dims():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = rand(ks[0], (2, 64, 32))
    w1 = rand(ks[1], (32, 128), scale=0.1)
    w2 = rand(ks[2], (128, 32), scale=0.1)
    out = fused_mlp(x, w1, w2, block_n=64, block_h=64, interpret=True)
    expect = ref.fused_mlp_ref(x, w1, None, w2, None)
    assert out.shape == (2, 64, 32)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 48)])
def test_flash_attention(hq, hkv, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, n, dh = 2, 128, 32
    q = rand(ks[0], (b, hq, n, dh))
    k = rand(ks[1], (b, hkv, n, dh))
    v = rand(ks[2], (b, hkv, n, dh))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (1, 2, 64, 64), dtype)
    k = rand(ks[1], (1, 2, 64, 64), dtype)
    v = rand(ks[2], (1, 2, 64, 64), dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               expect.astype(jnp.float32),
                               rtol=TOL[dtype], atol=TOL[dtype] * 5)


def test_flash_attention_q_offset_decode_suffix():
    """Attention over a suffix with q_offset == causal over the prefix."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    b, h, n, dh = 1, 2, 128, 32
    q = rand(ks[0], (b, h, n, dh))
    k = rand(ks[1], (b, h, n, dh))
    v = rand(ks[2], (b, h, n, dh))
    full = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    tail = flash_attention(q[:, :, 96:], k, v, q_offset=96,
                           block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(tail, full[:, :, 96:], rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_masked_ref():
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    b, hq, hkv, s, dh = 3, 8, 2, 256, 64
    q = rand(ks[0], (b, hq, dh))
    kc = rand(ks[1], (b, hkv, s, dh))
    vc = rand(ks[2], (b, hkv, s, dh))
    lens = jnp.array([100, 256, 7])
    out = decode_attention(q, kc, vc, lens, block_k=64, interpret=True)
    for i in range(b):
        li = int(lens[i])
        expect = ref.attention_ref(q[i:i + 1, :, None], kc[i:i + 1, :, :li],
                                   vc[i:i + 1, :, :li], causal=False)
        np.testing.assert_allclose(out[i], expect[0, :, 0],
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 256, 128, 64, 64, 128),
    (64, 64, 64, 64, 64, 64),
    (256, 512, 384, 128, 128, 256),
])
def test_int8_matmul_exact(m, k, n, bm, bn, bk):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    xq = jax.random.randint(ks[0], (m, k), -127, 128, jnp.int8)
    wq = jax.random.randint(ks[1], (k, n), -127, 128, jnp.int8)
    out = int8_matmul(xq, wq, block_m=bm, block_n=bn, block_k=bk,
                      interpret=True)
    expect = ref.int8_matmul_ref(xq, wq)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(out, expect)   # int math: exact


def test_int8_matmul_fused_rescale():
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    m, k, n = 128, 128, 128
    xq = jax.random.randint(ks[0], (m, k), -127, 128, jnp.int8)
    wq = jax.random.randint(ks[1], (k, n), -127, 128, jnp.int8)
    xs = jnp.asarray(0.013)
    ws = jax.random.uniform(ks[2], (n,)) * 0.05
    out = int8_matmul(xq, wq, xs, ws, block_m=64, block_n=64, block_k=64,
                      interpret=True)
    expect = ref.int8_matmul_ref(xq, wq, xs, ws)
    np.testing.assert_allclose(out, expect, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# vita_msa (paper-faithful per-head fused MSA)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,d,h,dh", [(64, 96, 3, 32), (256, 768, 12, 64),
                                      (49, 96, 3, 32)])
def test_vita_msa(n, d, h, dh):
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    z = rand(ks[0], (n, d), scale=0.3)
    wq = rand(ks[1], (h, d, dh), scale=0.05)
    wk = rand(ks[2], (h, d, dh), scale=0.05)
    wv = rand(ks[3], (h, d, dh), scale=0.05)
    out = vita_msa(z, wq, wk, wv, interpret=True)
    expect = ref.vita_msa_ref(z, wq, wk, wv)
    assert out.shape == (h, n, dh)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_vita_msa_head_independence():
    """Each head's output depends only on its own weight slice — the
    head-level pipeline invariant that lets ViTA stage one head at a time."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    n, d, h, dh = 32, 48, 4, 12
    z = rand(ks[0], (n, d), scale=0.3)
    wq = rand(ks[1], (h, d, dh), scale=0.1)
    wk = rand(ks[2], (h, d, dh), scale=0.1)
    wv = rand(ks[3], (h, d, dh), scale=0.1)
    base = np.asarray(vita_msa(z, wq, wk, wv, interpret=True))
    wq2 = wq.at[2].set(0.0)   # clobber head 2 only
    out = np.asarray(vita_msa(z, wq2, wk, wv, interpret=True))
    np.testing.assert_allclose(out[[0, 1, 3]], base[[0, 1, 3]],
                               rtol=1e-6, atol=1e-6)
    assert not np.allclose(out[2], base[2])


@pytest.mark.parametrize("b", [1, 3, 8])
@pytest.mark.parametrize("h", [3, 12])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_vita_msa_batched_grid(b, h, dtype):
    """The (batch, head) grid covers the whole batch in one pallas_call and
    matches the per-image oracle for every image."""
    n, d, dh = 49, 96, 16
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    z = rand(ks[0], (b, n, d), dtype, 0.3)
    wq = rand(ks[1], (h, d, dh), dtype, 0.05)
    wk = rand(ks[2], (h, d, dh), dtype, 0.05)
    wv = rand(ks[3], (h, d, dh), dtype, 0.05)
    out = vita_msa_batched(z, wq, wk, wv, interpret=True)
    assert out.shape == (b, h, n, dh)
    expect = ref.vita_msa_batched_ref(z, wq, wk, wv)
    np.testing.assert_allclose(
        out.astype(jnp.float32), expect.astype(jnp.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 10)
    # agrees image-by-image with the single-image oracle
    for i in range(b):
        np.testing.assert_allclose(
            out[i].astype(jnp.float32),
            ref.vita_msa_ref(z[i], wq, wk, wv).astype(jnp.float32),
            rtol=TOL[dtype], atol=TOL[dtype] * 10)


@pytest.mark.parametrize("b,h", [(1, 3), (4, 12)])
def test_vita_msa_int8_matches_ref(b, h):
    n, d, dh = 64, 96, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 7)
    zq = jax.random.randint(ks[0], (b, n, d), -127, 128, jnp.int8)
    wq = jax.random.randint(ks[1], (h, d, dh), -127, 128, jnp.int8)
    wk = jax.random.randint(ks[2], (h, d, dh), -127, 128, jnp.int8)
    wv = jax.random.randint(ks[3], (h, d, dh), -127, 128, jnp.int8)
    xs = jnp.asarray(0.011)
    qs = jax.random.uniform(ks[4], (h, dh), minval=1e-3, maxval=0.03)
    ss = jax.random.uniform(ks[5], (h, dh), minval=1e-3, maxval=0.03)
    vs = jax.random.uniform(ks[6], (h, dh), minval=1e-3, maxval=0.03)
    out = vita_msa_int8(zq, wq, wk, wv, xs, qs, ss, vs, interpret=True)
    assert out.shape == (b, h, n, dh) and out.dtype == jnp.float32
    expect = ref.vita_msa_int8_ref(zq, wq, wk, wv, xs, qs, ss, vs)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def test_vita_msa_int8_approximates_float():
    """Quantize a float problem per-(head, out-channel) and check the int8
    kernel tracks the float kernel within PTQ error."""
    from repro.core.quant import INT8_MAX, amax_scale, quantize
    b, n, d, h, dh = 2, 32, 48, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(14), 4)
    z = rand(ks[0], (b, n, d), scale=0.3)
    ws = [rand(k, (h, d, dh), scale=0.05) for k in ks[1:]]
    qts = [quantize(w, amax_scale(w, axis=(1,))) for w in ws]
    xs = amax_scale(z)
    zq = jnp.clip(jnp.round(z / xs), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    out = vita_msa_int8(
        zq, *[q.values for q in qts], xs,
        *[q.scale.reshape(h, dh) for q in qts], interpret=True)
    expect = ref.vita_msa_batched_ref(z, *ws)
    np.testing.assert_allclose(out, expect, rtol=0.1, atol=0.02)


# -- windowed (Swin W-MSA) mode: windows folded into the batch axis ---------


def _window_problem(key, b, n_w, n, d, h, dh, shifted=True):
    ks = jax.random.split(key, 6)
    z = rand(ks[0], (b * n_w, n, d), scale=0.3)
    ws = [rand(k, (h, d, dh), scale=0.05) for k in ks[1:4]]
    bias = rand(ks[4], (h, n, n), scale=0.5)
    if shifted:
        keep = jax.random.bernoulli(ks[5], 0.75, (n_w, n, n))
        keep = keep | jnp.eye(n, dtype=bool)[None]   # never mask the diagonal
        mask = jnp.where(keep, 0.0, -1e30)
    else:
        mask = jnp.zeros((n_w, n, n))
    return z, ws, bias, mask


@pytest.mark.parametrize("b,n_w,h", [(1, 4, 3), (3, 4, 6), (2, 1, 3)])
def test_vita_msa_windowed_matches_ref(b, n_w, h):
    """W-MSA on the same (batch, head) grid: per-head rel-pos bias selected
    by the head index, per-window region mask selected by i % nW."""
    n, d, dh = 49, 48, 16
    z, ws, bias, mask = _window_problem(jax.random.PRNGKey(21),
                                        b, n_w, n, d, h, dh)
    out = vita_msa_batched(z, *ws, bias, mask, interpret=True)
    assert out.shape == (b * n_w, h, n, dh)
    expect = ref.vita_msa_batched_ref(z, *ws, bias, mask)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_vita_msa_windowed_mask_isolates_regions():
    """A masked-out (cross-region) key must not influence the output:
    perturbing its value row is invisible wherever the mask forbids it."""
    b, n_w, n, d, h, dh = 1, 2, 16, 24, 2, 12
    z, ws, bias, _ = _window_problem(jax.random.PRNGKey(22),
                                     b, n_w, n, d, h, dh, shifted=False)
    # window 0: token 0 may only attend to tokens < 8; window 1: unmasked
    mask = np.zeros((n_w, n, n), np.float32)
    mask[0, 0, 8:] = -1e30
    mask = jnp.asarray(mask)
    base = np.asarray(vita_msa_batched(z, *ws, bias, mask, interpret=True))
    z2 = z.at[0, 12].add(7.0)        # masked-out token in window 0
    out = np.asarray(vita_msa_batched(z2, *ws, bias, mask, interpret=True))
    # query 0 of window 0 can't see token 12 -> unchanged
    np.testing.assert_allclose(out[0, :, 0], base[0, :, 0],
                               rtol=1e-5, atol=1e-5)
    # but unmasked queries in the same window do see it
    assert not np.allclose(out[0, :, 1], base[0, :, 1])


@pytest.mark.parametrize("b,n_w,h", [(2, 4, 3)])
def test_vita_msa_int8_windowed_matches_ref(b, n_w, h):
    """int8 W-MSA: requant in-kernel, bias+mask added in the fp32 softmax
    stage (ViTA's high-precision softmax unit)."""
    n, d, dh = 49, 48, 16
    ks = jax.random.split(jax.random.PRNGKey(23), 8)
    zq = jax.random.randint(ks[0], (b * n_w, n, d), -127, 128, jnp.int8)
    wq = jax.random.randint(ks[1], (h, d, dh), -127, 128, jnp.int8)
    wk = jax.random.randint(ks[2], (h, d, dh), -127, 128, jnp.int8)
    wv = jax.random.randint(ks[3], (h, d, dh), -127, 128, jnp.int8)
    xs = jnp.asarray(0.013)
    qs = jax.random.uniform(ks[4], (h, dh), minval=1e-3, maxval=0.03)
    ss = jax.random.uniform(ks[5], (h, dh), minval=1e-3, maxval=0.03)
    vs = jax.random.uniform(ks[6], (h, dh), minval=1e-3, maxval=0.03)
    bias = rand(ks[7], (h, n, n), scale=0.5)
    keep = jax.random.bernoulli(ks[7], 0.8, (n_w, n, n))
    keep = keep | jnp.eye(n, dtype=bool)[None]
    mask = jnp.where(keep, 0.0, -1e30)
    out = vita_msa_int8(zq, wq, wk, wv, xs, qs, ss, vs, bias, mask,
                        interpret=True)
    assert out.shape == (b * n_w, h, n, dh) and out.dtype == jnp.float32
    expect = ref.vita_msa_int8_ref(zq, wq, wk, wv, xs, qs, ss, vs,
                                   bias, mask)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


# -- optional per-head Q/K/V projection bias --------------------------------


def test_vita_msa_qkv_bias_matches_ref_and_default_is_bias_free():
    b, n, d, h, dh = 2, 32, 48, 4, 12
    ks = jax.random.split(jax.random.PRNGKey(31), 5)
    z = rand(ks[0], (b, n, d), scale=0.3)
    ws = [rand(k, (h, d, dh), scale=0.05) for k in ks[1:4]]
    qb = rand(ks[4], (3, h, dh), scale=0.2)
    out = vita_msa_batched(z, *ws, None, None, qb, interpret=True)
    expect = ref.vita_msa_batched_ref(z, *ws, qkv_bias=qb)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)
    # the bias is live, and omitting it reproduces the bias-free kernel
    base = vita_msa_batched(z, *ws, interpret=True)
    assert not np.allclose(out, base, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(base, ref.vita_msa_batched_ref(z, *ws),
                               rtol=2e-5, atol=2e-5)


def test_vita_msa_qkv_bias_windowed():
    b, n_w, n, d, h, dh = 2, 4, 49, 48, 3, 16
    z, ws, bias, mask = _window_problem(jax.random.PRNGKey(32),
                                        b, n_w, n, d, h, dh)
    qb = rand(jax.random.PRNGKey(33), (3, h, dh), scale=0.2)
    out = vita_msa_batched(z, *ws, bias, mask, qb, interpret=True)
    expect = ref.vita_msa_batched_ref(z, *ws, bias, mask, qb)
    np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)


def test_vita_msa_int8_qkv_bias_matches_ref():
    """int8 path: the float bias joins after the requant, in fp32 (the
    high-precision softmax stage) — checkpoint qkv.bias needs no quant."""
    b, n, d, h, dh = 2, 32, 48, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(34), 8)
    zq = jax.random.randint(ks[0], (b, n, d), -127, 128, jnp.int8)
    wq, wk, wv = (jax.random.randint(k, (h, d, dh), -127, 128, jnp.int8)
                  for k in ks[1:4])
    xs = jnp.asarray(0.012)
    qs, ss, vs = (jax.random.uniform(k, (h, dh), minval=1e-3, maxval=0.03)
                  for k in ks[4:7])
    qb = rand(ks[7], (3, h, dh), scale=0.2)
    out = vita_msa_int8(zq, wq, wk, wv, xs, qs, ss, vs, None, None, qb,
                        interpret=True)
    expect = ref.vita_msa_int8_ref(zq, wq, wk, wv, xs, qs, ss, vs,
                                   qkv_bias=qb)
    # int8-range scores make the softmax sharp; fp32 reassociation between
    # the kernel and the einsum oracle shows up at ~1e-4 relative
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)
    base = vita_msa_int8(zq, wq, wk, wv, xs, qs, ss, vs, interpret=True)
    assert not np.allclose(out, base, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# vita_layer (fused encoder layer: msa -> concat -> mlp, one kernel chain)
# ---------------------------------------------------------------------------


def _layer_problem(key, b, n, d, h, m):
    ks = jax.random.split(key, 8)
    dh = d // h
    x = rand(ks[0], (b, n, d), scale=0.3)
    ws = [rand(k, (h, d, dh), scale=0.05) for k in ks[1:4]]
    w_msa = rand(ks[4], (d, d), scale=0.05)
    lns = (jnp.ones(d), jnp.zeros(d), jnp.ones(d), jnp.zeros(d))
    mlp = (rand(ks[5], (d, m), scale=0.05), rand(ks[6], (m,), scale=0.05),
           rand(ks[7], (m, d), scale=0.05), jnp.zeros((d,)))
    return x, ws, w_msa, lns, mlp


@pytest.mark.parametrize("b,n,d,h,m", [(2, 16, 48, 4, 96),
                                       (1, 49, 48, 3, 192),
                                       (3, 64, 96, 4, 384)])
def test_vita_layer_matches_ref(b, n, d, h, m):
    x, ws, w_msa, lns, mlp = _layer_problem(jax.random.PRNGKey(41),
                                            b, n, d, h, m)
    out = vita_layer(x, *ws, w_msa, *lns, *mlp, interpret=True)
    expect = ref.vita_layer_ref(x, *ws, w_msa, *lns, *mlp)
    assert out.shape == x.shape
    np.testing.assert_allclose(out, expect, rtol=3e-5, atol=3e-5)


def test_vita_layer_matches_the_unfused_composition():
    """The fused chain == LN -> msa -> concat -> residual -> LN -> mlp ->
    residual composed from the per-phase oracles (phase-boundary math)."""
    b, n, d, h, m = 2, 32, 48, 4, 96
    x, ws, w_msa, lns, mlp = _layer_problem(jax.random.PRNGKey(42),
                                            b, n, d, h, m)
    out = vita_layer(x, *ws, w_msa, *lns, *mlp, interpret=True)
    z = ref.layer_norm_ref(x, lns[0], lns[1]).astype(x.dtype)
    sa = ref.vita_msa_batched_ref(z, *ws)
    h1 = x + sa.transpose(0, 2, 1, 3).reshape(b, n, d) @ w_msa
    z2 = ref.layer_norm_ref(h1, lns[2], lns[3]).astype(x.dtype)
    want = h1 + ref.fused_mlp_ref(z2, mlp[0], mlp[1], mlp[2], mlp[3],
                                  activation="gelu")
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_vita_layer_windowed_matches_ref():
    b, n_w, n, d, h, m = 2, 4, 49, 48, 3, 96
    x, ws, w_msa, lns, mlp = _layer_problem(jax.random.PRNGKey(43),
                                            b * n_w, n, d, h, m)
    bias = rand(jax.random.PRNGKey(44), (h, n, n), scale=0.5)
    keep = jax.random.bernoulli(jax.random.PRNGKey(45), 0.8, (n_w, n, n))
    mask = jnp.where(keep | jnp.eye(n, dtype=bool)[None], 0.0, -1e30)
    out = vita_layer(x, *ws, w_msa, *lns, *mlp, bias, mask, interpret=True)
    expect = ref.vita_layer_ref(x, *ws, w_msa, *lns, *mlp, bias, mask)
    np.testing.assert_allclose(out, expect, rtol=3e-5, atol=3e-5)


def _int8_layer_problem(key, b, n, d, h, m):
    from repro.core.quant import amax_scale, quantize, quantize_per_channel
    dh = d // h
    x, ws, w_msa, lns, mlp = _layer_problem(key, b, n, d, h, m)
    qkv = [quantize(w, amax_scale(w, axis=(1,))) for w in ws]
    qmsa = quantize_per_channel(w_msa)
    qup, qdown = quantize_per_channel(mlp[0]), quantize_per_channel(mlp[2])
    acts = jnp.asarray([0.01, 0.008, 0.012, 0.009], jnp.float32)
    args = (x, qkv[0].values, qkv[1].values, qkv[2].values, qmsa.values,
            qup.values, qdown.values, acts,
            *[q.scale.reshape(h, dh) for q in qkv],
            qmsa.scale, qup.scale, qdown.scale, *lns, mlp[1], mlp[3])
    return args


def test_vita_layer_int8_matches_ref():
    args = _int8_layer_problem(jax.random.PRNGKey(46), 2, 32, 48, 4, 96)
    out = vita_layer_int8(*args, interpret=True)
    expect = ref.vita_layer_int8_ref(*args)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


def test_vita_layer_int8_windowed_matches_ref():
    b, n_w, n, d, h, m = 1, 4, 49, 48, 3, 96
    args = _int8_layer_problem(jax.random.PRNGKey(47), b * n_w, n, d, h, m)
    bias = rand(jax.random.PRNGKey(48), (h, n, n), scale=0.5)
    keep = jax.random.bernoulli(jax.random.PRNGKey(49), 0.8, (n_w, n, n))
    mask = jnp.where(keep | jnp.eye(n, dtype=bool)[None], 0.0, -1e30)
    out = vita_layer_int8(*args, bias, mask, interpret=True)
    expect = ref.vita_layer_int8_ref(*args, bias, mask)
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# RG-LRU chunked scan kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,chunk", [(32, 8), (64, 64), (48, 16), (96, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_kernel(t, chunk, dtype):
    from repro.kernels.rglru_scan import rglru_scan
    b, w = 2, 24
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    a = jax.random.uniform(ks[0], (b, t, w), jnp.float32,
                           0.7, 0.99).astype(dtype)
    x = (jax.random.normal(ks[1], (b, t, w)) * 0.1).astype(dtype)
    out = rglru_scan(a, x, chunk=chunk, interpret=True)
    h = jnp.zeros((b, w), jnp.float32)
    outs = []
    for i in range(t):
        h = a[:, i].astype(jnp.float32) * h + x[:, i].astype(jnp.float32)
        outs.append(h)
    expect = jnp.stack(outs, 1)
    np.testing.assert_allclose(out.astype(jnp.float32), expect,
                               rtol=TOL[dtype], atol=TOL[dtype] * 5)


def test_linear_recurrence_backends_agree():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(12), 2)
    a = jax.random.uniform(ks[0], (2, 40, 8), minval=0.5, maxval=0.99)
    b = jax.random.normal(ks[1], (2, 40, 8)) * 0.1
    np.testing.assert_allclose(
        ops.linear_recurrence(a, b, backend="pallas"),
        ops.linear_recurrence(a, b, backend="xla"),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                ("gpu", None)])
def test_kernels_interpret_on_cpu_only(monkeypatch, platform, interpret):
    """Kernels compile on the TPU and are interpreted on the CPU; on any
    other platform the dispatch layer refuses instead of interpreting."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_ON_TPU", False)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    if interpret is None:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops._interp()
    else:
        assert ops._interp() is interpret
    monkeypatch.setattr(ops, "_ON_TPU", True)
    assert ops._interp() is False
