"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.knn.kernel import knn
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


def _knn_case(order, Q, N):
    """Queries and points for one gated-selection case: N(0, 1) points,
    the same points in order of falling distance to the queries (bunched
    near one centre, so every block holds k candidates for each), or a
    shuffled copy of each point (ties across blocks)."""
    ks = jax.random.split(jax.random.fold_in(RNG, 7), 3)
    if order == "falling":
        q = 0.01 * jax.random.normal(ks[0], (Q, 16), jnp.float32)
        x = jax.random.normal(ks[1], (N, 16), jnp.float32)
        return q, x[jnp.argsort(-jnp.sum(x * x, -1))]
    q = jax.random.normal(ks[0], (Q, 16), jnp.float32)
    if order == "duplicated":
        x = jax.random.normal(ks[1], (N // 2, 16), jnp.float32)
        return q, jax.random.permutation(ks[2], jnp.concatenate([x, x]))
    return q, jax.random.normal(ks[1], (N, 16), jnp.float32)


def _assert_knn_exact(q, x, d, i, k):
    """Distances as ``knn_ref``'s; each query's indices distinct, in
    range, and judged by their exact distance (ties may permute)."""
    dr, _ = knn_ref(q, x, k)
    np.testing.assert_allclose(d, dr, atol=1e-4, rtol=1e-4)
    i = np.asarray(i)
    assert ((i >= 0) & (i < x.shape[0])).all()
    assert all(len(set(row)) == k for row in i.tolist())
    gathered = jnp.sum((q[:, None, :] - x[i]) ** 2, -1)
    np.testing.assert_allclose(jnp.sort(gathered, 1), dr, atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("order,Q,N,k,block_q,block_n,pad", [
    ("falling", 32, 1024, 10, 32, 256, 0),    # every block runs k passes
    ("duplicated", 16, 1024, 10, 16, 256, 0),  # ties across blocks
    ("normal", 16, 700, 10, 16, 256, 68),     # padded tail, n_valid
    ("normal", 40, 1000, 10, 16, 256, 0),     # block_q 16, queries padded
    ("normal", 64, 1500, 10, 32, 128, 0),     # block_q 32
    ("normal", 16, 200, 20, 16, 16, 0),       # k over a block's points
])
def test_knn_gated_selection(order, Q, N, k, block_q, block_n, pad):
    """The gated kernel returns the exact top-k; padding rows past
    ``n_valid``, each a copy of a query, are never candidates."""
    q, x = _knn_case(order, Q, N)
    if pad:
        data = jnp.concatenate([x, jnp.tile(q, (-(-pad // Q), 1))[:pad]])
        d, i = knn_op(q, data, k=k, block_q=block_q, block_n=block_n,
                      n_valid=N)
    else:
        d, i = knn_op(q, x, k=k, block_q=block_q, block_n=block_n)
    _assert_knn_exact(q, x, d, i, k)


def _gate_passes(q, x, k, block_n):
    """The gate counted in NumPy: per block, the most over the queries of
    ``min(k, points strictly below the running k-th)``, summed."""
    q64, x64 = np.asarray(q, np.float64), np.asarray(x, np.float64)
    d = ((q64[:, None, :] - x64[None]) ** 2).sum(-1)
    best = np.full((q.shape[0], k), np.inf)
    passes = 0
    for lo in range(0, x.shape[0], block_n):
        blk = d[:, lo:lo + block_n]
        passes += min(k, int((blk < best[:, -1:]).sum(1).max()))
        best = np.sort(np.concatenate([best, blk], 1), 1)[:, :k]
    return passes


@pytest.mark.parametrize("order", ["normal", "falling"])
def test_knn_passes_counts_the_gate(order):
    """``passes`` is the gate's count, per query block: fewer than k a
    block on N(0, 1) points, and k every block when each block holds k
    candidates for some query."""
    k, block_n, n_blocks = 10, 128, 16
    q, x = _knn_case(order, 64, n_blocks * block_n)
    d, i, passes = knn(q, x, k=k, block_q=32, block_n=block_n,
                       interpret=True)
    _assert_knn_exact(q, x, d, i, k)
    want = [_gate_passes(q[:32], x, k, block_n),
            _gate_passes(q[32:], x, k, block_n)]
    assert passes.tolist() == want
    if order == "falling":
        assert want == [k * n_blocks] * 2
    else:
        assert max(want) < k * n_blocks


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
