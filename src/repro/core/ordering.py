"""Per-dimension sort order of the training coordinates.

``fit`` and the KMG coarse hierarchy sort every input dimension, invert the
permutation and separate exact ties. Written the obvious way
(``jnp.argsort`` of float64 keys, ``argsort`` of the permutation, a float64
``cumsum``) these three ops dominate the TPU compile of the float64 fit:
XLA emulates 64-bit floats there, and its sort comparator and prefix scan
over emulated doubles compile for minutes. The forms below give the same
order and the same tie bumps from 32-bit integer work only:

  * ``argsort_rows`` sorts by order-preserving int32 keys — the float32
    rounding of each value and the float32 rounding of its remainder for
    64-bit input, a lexicographic pair that orders doubles exactly down to
    about 2^-48 relative (closer values keep their input order, as a stable
    sort keeps ties; ``separate_ties`` then spaces them);
  * ``inverse_perm`` scatters instead of sorting a second time;
  * ``separate_ties`` counts ties with an int32 prefix sum and scales once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["argsort_rows", "inverse_perm", "separate_ties"]


def _sortable_int32(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the float order."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def argsort_rows(x: jax.Array) -> jax.Array:
    """Stable int32 argsort of a float array along its last axis."""
    if jnp.dtype(x.dtype).itemsize > 4:
        hi = x.astype(jnp.float32)
        lo = (x - hi.astype(x.dtype)).astype(jnp.float32)
        keys = (_sortable_int32(hi), _sortable_int32(lo))
    else:
        keys = (_sortable_int32(x.astype(jnp.float32)),)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jax.lax.sort(keys + (idx,), dimension=x.ndim - 1,
                        num_keys=len(keys), is_stable=True)[-1]


def inverse_perm(idx: jax.Array) -> jax.Array:
    """Inverse of permutations along the last axis (leading dims batch)."""
    n = idx.shape[-1]
    flat = idx.reshape(-1, n)
    rows = jnp.arange(flat.shape[0])[:, None]
    iota = jnp.broadcast_to(jnp.arange(n, dtype=idx.dtype), flat.shape)
    return jnp.zeros_like(flat).at[rows, flat].set(iota).reshape(idx.shape)


def separate_ties(xs: jax.Array, step: jax.Array) -> jax.Array:
    """Strictly increasing copy of sorted rows ``xs`` (..., n): every later
    member of a run of equal values moves up by ``step`` (broadcast against
    the rows) times its count of ties so far — order preserved."""
    ties = jnp.cumsum((jnp.diff(xs, axis=-1) <= 0).astype(jnp.int32),
                      axis=-1)
    return xs.at[..., 1:].add(ties.astype(xs.dtype) * step)
