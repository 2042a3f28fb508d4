"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import block_topk, qsgd_quantize, sign_ef_compress
from repro.kernels import ref
from repro.kernels.qsgd import qsgd_pallas
from repro.kernels.sign_ef import sign_ef_pallas
from repro.kernels.topk_mask import block_topk_pallas

SHAPES_2D = [(8, 128), (8, 1024), (16, 256), (64, 128)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("k", [1, 8, 32])
def test_topk_kernel_matches_oracle(shape, dtype, k):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    got = block_topk_pallas(x, k, interpret=True)
    want = ref.block_topk_threshold_ref(x.astype(jnp.float32), k).astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("k", [4, 16])
def test_topk_kernel_close_to_exact_topk(shape, k):
    """Bisection-threshold selection ~= exact sort-based top-k."""
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    got = block_topk_pallas(x, k, interpret=True)
    exact = ref.block_topk_ref(x, k)
    inter = np.sum((np.asarray(got) != 0) & (np.asarray(exact) != 0))
    assert inter >= 0.9 * k * shape[0]  # >=90% mask overlap


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("levels", [4, 256])
def test_qsgd_kernel_matches_oracle(shape, dtype, levels):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, shape).astype(dtype)
    u = jax.random.uniform(jax.random.PRNGKey(3), shape, jnp.float32)
    norm = jnp.linalg.norm(x.astype(jnp.float32).reshape(-1)).reshape(1, 1)
    got = qsgd_pallas(x, u, norm, levels, interpret=True)
    want = ref.qsgd_ref(x, u, norm[0, 0], levels)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-3)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sign_ef_kernel_matches_oracle(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(4), shape).astype(dtype)
    e = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
    c, e2 = sign_ef_pallas(x, e, interpret=True)
    cw, ew = ref.sign_ef_ref(x, e)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cw), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(e2), np.asarray(ew), rtol=1e-5,
                               atol=1e-5)


def test_sign_ef_identity_property():
    """c + e' == x + e (the EF invariant survives the fusion)."""
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 256))
    e = jax.random.normal(jax.random.PRNGKey(7), (16, 256))
    c, e2 = sign_ef_pallas(x, e, interpret=True)
    np.testing.assert_allclose(np.asarray(c + e2), np.asarray(x + e),
                               rtol=1e-5, atol=1e-5)


# --- public wrappers: arbitrary shapes (padding path) ---
@pytest.mark.parametrize("shape", [(100,), (3, 777), (5, 7, 11)])
def test_wrappers_arbitrary_shapes(shape):
    x = jax.random.normal(jax.random.PRNGKey(8), shape)
    out = block_topk(x, 0.05, interpret=True)
    assert out.shape == x.shape
    q = qsgd_quantize(jax.random.PRNGKey(9), x, interpret=True)
    assert q.shape == x.shape
    c, e2 = sign_ef_compress(x, jnp.zeros(shape), interpret=True)
    np.testing.assert_allclose(np.asarray(c + e2), np.asarray(x), rtol=1e-5,
                               atol=1e-6)


def test_qsgd_wrapper_unbiased_statistically():
    x = jax.random.normal(jax.random.PRNGKey(10), (64,))
    qs = jnp.stack([qsgd_quantize(jax.random.PRNGKey(i), x, levels=8,
                                  interpret=True) for i in range(300)])
    np.testing.assert_allclose(np.asarray(qs.mean(0)), np.asarray(x),
                               atol=0.25)


# --- exact per-row top-k (topk_rows): radix select against a sort ---
def _ties(key, shape):
    """Halves in [-3, 3]: many exact ties and exact zeros per row."""
    return jnp.round(2 * jax.random.normal(key, shape)) / 2


# (x, k): both kernels (row blocks; streamed vreg chunks for a few wide
# float32 rows), ragged column edges, ties, zeros, bf16, k >= D, fractional k
ROW_TOPK_CASES = {
    "wide_ragged": lambda key: (jax.random.normal(key, (3, 2 * 16384 + 1000)),
                                300),
    "streamed_ragged": lambda key: (
        jax.random.normal(key, (3, 2 * 131072 + 1000)), 2000),
    "streamed_dense": lambda key: (
        jax.random.normal(key, (2, 128 * 4096 + 128 * 37)), 5291),
    "narrow_k1": lambda key: (jax.random.normal(key, (4096, 32)), 1),
    "ties_zeros": lambda key: (_ties(key, (8, 4096)), 700),
    "ties_zeros_streamed": lambda key: (_ties(key, (2, 300000)), 30000),
    "bf16": lambda key: (jax.random.normal(key, (4, 5000), jnp.bfloat16), 50),
    "k_equals_d": lambda key: (jax.random.normal(key, (2, 1000)), 1000),
    "k_above_d": lambda key: (jax.random.normal(key, (2, 1000)), 5000),
    "fractional_k": lambda key: (jax.random.normal(key, (4, 128)), 4.5),
    "fractional_k_streamed": lambda key: (
        jax.random.normal(key, (2, 300000)), 7.5),
}


def _topk_rows_oracle(x, k):
    """Keep |x| >= the row's ceil(k)-th largest |x| (every |x| past D)."""
    x = np.asarray(x)
    a = np.abs(x.astype(np.float32))
    kk = int(np.ceil(k))
    out = np.zeros_like(x)
    for r in range(x.shape[0]):
        thr = np.sort(a[r])[::-1][kk - 1] if kk <= x.shape[1] else 0.0
        out[r] = np.where(a[r] >= thr, x[r], 0)
    return out


@pytest.mark.parametrize("mode", ["interpret", "jit"])
@pytest.mark.parametrize("case", sorted(ROW_TOPK_CASES))
def test_topk_rows_is_exact_topk(case, mode):
    from repro.kernels import topk_rows
    x, k = ROW_TOPK_CASES[case](jax.random.PRNGKey(11))
    got = topk_rows(x, jnp.float32(k), mode=mode)  # k traced
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), _topk_rows_oracle(x, k))
