"""Sparse position coding (paper §II.A.5, Alg. 4)."""
import numpy as np
import pytest

from repro.core.compression.coding import (decode_positions, elias_gamma_bits,
                                           encode_positions, naive_sparse_bits,
                                           sparse_message_bits)


def test_paper_example_roundtrip():
    """The d=24, phi=1/8 example from the chapter: indices {1, 5, 17}."""
    idx = [1, 5, 17]
    bits, bs = encode_positions(idx, 24)
    assert bs == 8
    assert decode_positions(bits, 24, bs) == idx


@pytest.mark.parametrize("d,nnz,seed", [(64, 4, 0), (1024, 10, 1),
                                        (4096, 41, 2), (100, 99, 3),
                                        (128, 1, 4)])
def test_roundtrip_random(d, nnz, seed):
    rng = np.random.default_rng(seed)
    idx = sorted(rng.choice(d, nnz, replace=False).tolist())
    bits, bs = encode_positions(idx, d)
    assert decode_positions(bits, d, bs) == idx


def test_bitstring_length_matches_analytic():
    rng = np.random.default_rng(0)
    d, nnz = 4096, 32
    idx = sorted(rng.choice(d, nnz, replace=False).tolist())
    bits, bs = encode_positions(idx, d)
    expected = sparse_message_bits(d, nnz, value_bits=0)
    assert abs(len(bits) - expected) <= 1


def test_block_coding_beats_naive_at_low_phi():
    d = 1 << 20
    for nnz in (100, 1000, 10_000):
        assert sparse_message_bits(d, nnz) < naive_sparse_bits(d, nnz)


def test_elias_bits():
    assert elias_gamma_bits([1]) == 1
    assert elias_gamma_bits([2]) == 3
    assert elias_gamma_bits([4, 4]) == 10


# ---------------------------------------------------------------------------
# finite-field fixed-point codec (secure aggregation, core/privacy)
# ---------------------------------------------------------------------------
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.compression.coding import (field_scale, from_field,  # noqa: E402
                                           to_field)

# width=32 bounds must be float32 values themselves (1e-3 is not one)
CLIPS = st.floats(float(np.float32(1e-3)), 1e3, allow_nan=False, width=32)
VALS = st.floats(-1e3, 1e3, allow_nan=False, width=32)


@given(st.lists(VALS, min_size=1, max_size=64), CLIPS,
       st.integers(8, 24))
@settings(max_examples=60, deadline=None)
def test_field_roundtrip_within_quantization_step(vals, clip, fb):
    """decode(encode(x)) is x clamped to [-clip, clip], up to half a
    quantization step 1/(2*scale)."""
    x = jnp.asarray(vals, jnp.float32)
    q = to_field(x, clip, float(fb))
    back = np.asarray(from_field(q, clip, float(fb)))
    want = np.clip(np.asarray(x), -clip, clip)
    step = 1.0 / float(field_scale(clip, float(fb)))
    np.testing.assert_allclose(back, want, atol=0.5 * step + 1e-6 * clip)


@given(st.lists(VALS, min_size=1, max_size=32), CLIPS,
       st.integers(8, 24))
@settings(max_examples=60, deadline=None)
def test_field_exact_reencode(vals, clip, fb):
    """Field elements are a fixed point of the codec: encoding the decode
    reproduces the same uint32 words exactly."""
    q = to_field(jnp.asarray(vals, jnp.float32), clip, float(fb))
    back = from_field(q, clip, float(fb))
    q2 = to_field(back, clip, float(fb))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))


@given(st.integers(2, 64), st.integers(8, 16), st.data())
@settings(max_examples=40, deadline=None)
def test_field_sum_exact_within_headroom(m, fb, data):
    """A modular sum of m encodings decodes to the exact sum of the
    individual decodes while m * 2^(fb-1) < 2^31 (no int32 overflow)."""
    assert m * (1 << (fb - 1)) < (1 << 31)
    clip = 1.0
    rows = np.asarray(
        data.draw(st.lists(st.lists(st.floats(-1.0, 1.0, width=32),
                                    min_size=4, max_size=4),
                           min_size=m, max_size=m)), np.float32)
    q = to_field(jnp.asarray(rows), clip, float(fb))
    qsum = np.asarray(q).astype(np.uint64).sum(0).astype(np.uint32)
    got = np.asarray(from_field(jnp.asarray(qsum), clip, float(fb)))
    want = np.asarray(from_field(q, clip, float(fb))).sum(0)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_field_negative_wraps_to_ring_top():
    """Negative values occupy the top of Z_{2^32} (two's complement)."""
    q = np.asarray(to_field(jnp.asarray([-1.0, 1.0]), 1.0, 16.0))
    assert q.dtype == np.uint32
    assert q[0] > (1 << 31) and q[1] < (1 << 31)
    # and the pair cancels modularly, as secagg relies on
    assert (int(q[0]) + int(q[1])) % (1 << 32) == 0
