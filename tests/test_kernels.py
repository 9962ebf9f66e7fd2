"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.knn.ops import knn_op, knn_ref
from repro.kernels.stencil_dilate.ops import dilate_iters_ref, dilate_op
from repro.kernels.systolic_matmul.ops import (conv_im2col_ref, conv_op,
                                               matmul_op, matmul_ref)

RNG = jax.random.PRNGKey(0)


# -- flash attention ----------------------------------------------------------

@pytest.mark.parametrize("B,H,K,Sq,Sk,d", [
    (1, 2, 2, 128, 128, 64),
    (2, 4, 2, 64, 64, 32),       # GQA
    (1, 8, 1, 128, 128, 64),     # MQA
    (1, 2, 2, 64, 256, 64),      # decode-style Sq<Sk
    (1, 2, 2, 100, 200, 64),     # unaligned → pad path
])
def test_flash_attention_shapes(B, H, K, Sq, Sk, d):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, H, Sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, K, Sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, K, Sk, d), jnp.float32)
    out = flash_attention_op(q, k, v, block_q=64, block_k=64)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kwargs", [
    {"window": 32}, {"softcap": 50.0}, {"causal": False},
    {"window": 64, "softcap": 30.0},
])
def test_flash_attention_features(kwargs):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
    out = flash_attention_op(q, k, v, block_q=64, block_k=64, **kwargs)
    ref = attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.bfloat16)
    out = flash_attention_op(q, k, v, block_q=64, block_k=64)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=2e-2, rtol=2e-2)


# -- stencil ------------------------------------------------------------------

@pytest.mark.parametrize("h,w,iters,br", [
    (256, 128, 1, 64), (256, 128, 3, 128), (128, 256, 2, 128),
    (512, 128, 1, 256),
])
def test_dilate(h, w, iters, br):
    img = jax.random.normal(RNG, (h, w), jnp.float32)
    out = dilate_op(img, iters=iters, block_rows=br)
    ref = dilate_iters_ref(img, iters)
    np.testing.assert_allclose(out, ref)


def test_dilate_monotone():
    img = jax.random.normal(RNG, (128, 128), jnp.float32)
    out = dilate_op(img, iters=1, block_rows=64)
    assert bool(jnp.all(out >= img))          # dilation never shrinks


# -- knn ----------------------------------------------------------------------

@pytest.mark.parametrize("Q,N,D,k", [
    (32, 500, 8, 5), (64, 1000, 16, 10), (16, 2048, 2, 10),
    (33, 999, 32, 10),                        # unaligned
])
def test_knn(Q, N, D, k):
    q = jax.random.normal(RNG, (Q, D), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(RNG, 1), (N, D), jnp.float32)
    d, i = knn_op(q, x, k=k, block_q=32, block_n=256)
    dr, ir = knn_ref(q, x, k)
    np.testing.assert_allclose(d, dr, atol=1e-4, rtol=1e-4)
    # Indices may permute among ties — compare distances gathered by index.
    gathered = jnp.sum((q[:, None, :] - x[i]) ** 2, -1)
    np.testing.assert_allclose(gathered, dr, atol=1e-4, rtol=1e-4)


def test_knn_tail_mask_covers_padding():
    """Data padded beforehand, with rows that would be every query's
    nearest: ``n_valid`` masks them, and the result is the unpadded one."""
    q = jax.random.normal(RNG, (16, 16), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(RNG, 1), (300, 16), jnp.float32)
    padded = jnp.concatenate([x, jnp.tile(q, (14, 1))[:212]])   # 512 rows
    d, i = knn_op(q, padded, k=10, block_q=16, block_n=256, n_valid=300)
    dr, ir = knn_op(q, x, k=10, block_q=16, block_n=256)
    np.testing.assert_array_equal(d, dr)
    np.testing.assert_array_equal(i, ir)
    with pytest.raises(ValueError, match="not padded"):
        knn_op(q, x, k=10, block_q=16, block_n=256, n_valid=300)


# Float32 squared distances of N(0, 1) points in 16-D (about 32) are good
# to a few 1e-6; one bfloat16 rounding of the inputs moves them by 1e-2.
KNN_REF_ATOL = 1e-4


@pytest.mark.parametrize("dtype,agrees", [(jnp.float32, True),
                                          (jnp.bfloat16, False)])
def test_knn_ref_matches_float64(dtype, agrees):
    """``knn_ref`` in float32 agrees with a float64 NumPy search; the same
    computation in bfloat16 does not, so the tolerance can tell them."""
    q = jax.random.normal(RNG, (32, 16), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(RNG, 1), (2000, 16), jnp.float32)
    d, i = knn_ref(q.astype(dtype), x.astype(dtype), 10)
    q64, x64 = np.asarray(q, np.float64), np.asarray(x, np.float64)
    exact = ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    want = np.sort(exact, axis=1)[:, :10]
    err = np.abs(np.asarray(d, np.float64) - want).max()
    assert (err <= KNN_REF_ATOL) == agrees, err
    if agrees:
        np.testing.assert_allclose(np.take_along_axis(exact, np.asarray(i),
                                                      1), want,
                                   atol=KNN_REF_ATOL, rtol=0)


# -- systolic matmul ----------------------------------------------------------

@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (256, 256, 256, 128, 128, 128),
    (300, 200, 150, 128, 128, 64),            # unaligned
    (64, 512, 64, 64, 64, 256),
])
def test_matmul(M, K, N, bm, bn, bk):
    a = jax.random.normal(RNG, (M, K), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(RNG, 2), (K, N), jnp.float32)
    out = matmul_op(a, b, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(out, matmul_ref(a, b), atol=1e-3, rtol=1e-4)


def test_conv_vgg_style():
    x = jax.random.normal(RNG, (16, 16, 32), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(RNG, 3), (3, 3, 32, 64),
                          jnp.float32) * 0.1
    np.testing.assert_allclose(conv_op(x, w), conv_im2col_ref(x, w),
                               atol=1e-4, rtol=1e-4)
