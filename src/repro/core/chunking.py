"""Chunk-invariant reductions and per-client randomness for the fleet engine.

The chunked client pass (``fl/server.py`` / ``fl/runtime.py``) processes
clients in power-of-two blocks of ``chunk_size`` inside a ``lax.scan``, so
peak temporary memory is O(chunk * D) instead of O(N * D). The acceptance
contract is **bitwise** parity with the unchunked pass at small N, which
plain ``jnp.sum`` cannot deliver: XLA is free to associate a row reduction
differently for an (N, D) operand than for its (chunk, D) slices, and float
addition is not associative. Two primitives restore exactness:

``canonical_sum``
    A *fixed pairwise tree*: rows are zero-padded to the next power of two
    and adjacent pairs are folded, ``log2`` times — the left-complete
    binary tree over the row axis. After ``log2(c)`` fold levels, entry i
    is exactly the subtree sum of aligned block i of size c, so
    ``canonical_sum(all rows)`` equals ``canonical_sum(stacked per-block
    canonical sums)`` *bit for bit*, for every power-of-two chunk size.
    (Folding half-against-half instead would pair row i with row i + N/2 —
    a butterfly, under which contiguous blocks are *not* subtrees.) Both
    the chunked and the unchunked client passes reduce through this tree,
    which is what makes chunked-vs-unchunked parity exact rather than
    approximate.

``client_keys``
    Per-client PRNG keys derived as ``fold_in(key, client_id)``. The obvious
    ``jax.random.split(key, n)`` is *not* prefix-stable (``split(k, 8)`` is
    not a prefix of ``split(k, 16)``), so a chunked pass slicing split keys
    would diverge from the unchunked pass. ``fold_in`` keys depend only on
    the (key, client id) pair, making them chunk-invariant by construction.

Zero-padding is exact for the tree because IEEE-754 guarantees
``x + (+0.0) == x`` for every non-(-0.0) x; padded rows are +0.0.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    if n <= 0:
        raise ValueError(f"pow2_ceil needs n >= 1, got {n}")
    return 1 << (n - 1).bit_length()


def is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def canonical_sum(x: jnp.ndarray, valid: Optional[jnp.ndarray] = None
                  ) -> jnp.ndarray:
    """Sum over axis 0 through the canonical pairwise (adjacent-fold) tree.

    ``x``: (N, ...). ``valid``: optional (N,) 0/1 mask applied before the
    fold. Masked rows are *selected* to +0.0 (``jnp.where``), not multiplied
    by zero — ``x * 0.0`` is ``-0.0`` for negative x, and ``-0.0`` is not a
    bitwise-neutral padding element (``-0.0 + -0.0 == -0.0`` but
    ``+0.0 + -0.0 == +0.0``). Returns the (...) sum with a
    *chunking-invariant* bit pattern: for any power-of-two ``c``, summing
    aligned c-row blocks first and then folding the block sums yields the
    identical result (see module docstring).
    """
    if valid is not None:
        keep = (valid != 0).reshape((-1,) + (1,) * (x.ndim - 1))
        x = jnp.where(keep, x, jnp.zeros((), x.dtype))
    n = x.shape[0]
    if n == 0:
        raise ValueError("canonical_sum needs at least one row")
    p = pow2_ceil(n)
    if p != n:
        pad = [(0, p - n)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    while x.shape[0] > 1:
        # strided slices, not x[::2]: that lowers to a gather, which the TPU
        # compiler splits into D/32768 pieces (minutes of compile at D=1e8)
        x = _every_other(x, 0) + _every_other(x, 1)
    return x[0]


def _every_other(x: jnp.ndarray, start: int) -> jnp.ndarray:
    """Rows ``start``, ``start + 2``, ... of ``x``."""
    rest = x.ndim - 1
    return lax.slice(x, (start,) + (0,) * rest, x.shape, (2,) + (1,) * rest)


def canonical_mean(x: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
                   count: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``canonical_sum / count``; ``count`` defaults to N (or the mask sum),
    floored at one so an empty selection yields zeros, not NaN."""
    if count is None:
        count = (jnp.float32(x.shape[0]) if valid is None
                 else jnp.sum(valid.astype(jnp.float32)))
    return canonical_sum(x, valid) / jnp.maximum(count, 1.0)


def client_keys(key: jax.Array, ids: jnp.ndarray) -> jax.Array:
    """Chunk-invariant per-client keys: ``fold_in(key, id)`` per row.

    ``ids``: (n,) int32 global client ids (a block's slice of
    ``arange(N)``). Row i depends only on ``(key, ids[i])``, never on the
    batch size — the property ``jax.random.split`` lacks.
    """
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(ids)


def block_ids(block: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Global client ids covered by block index ``block`` (traced ok)."""
    return block * chunk + jnp.arange(chunk, dtype=jnp.int32)


def n_blocks(n: int, chunk: int) -> int:
    """Number of chunk-sized blocks covering n clients; validates chunk."""
    if not is_pow2(chunk):
        raise ValueError(f"chunk_size must be a power of two, got {chunk}")
    return -(-n // chunk)
