"""Kernel Packet (KP) and generalized-KP sparse factorizations.

Implements the paper's Theorem 3 (central / one-sided KPs), Theorems 5-6
(generalized KPs for the omega-derivative), and Algorithms 2-3:

    P^T k(X, X) P         = A^{-1} Phi        (A: half-bw q+1, Phi: half-bw q)
    P^T d_omega k(X,X) P  = B^{-1} Psi        (B: half-bw q+2, Psi: half-bw q+1)

with q = nu - 1/2. ``B`` is exactly the Matérn-(nu+1) KP coefficient matrix
(Appendix C), so one construction routine serves both.

TPU adaptation (vs the paper's sequential MATLAB loop): all n window systems
are solved at once, with per-window centering + column scaling (shift/scale
invariance of Eq. (9)) so ``exp(omega x)`` never overflows. Each window's
null vector comes from a rolled Householder QR computed elementwise over the
rows — lane-dense on the TPU, where a batched SVD of (2q+2, 2q+3) matrices
pads every matrix to an (8, 128) tile and compiles to a huge program.
Construction cost O(n * (2q+3)^3) fully parallel, instead of a length-n
sequential loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import matern as mk
from .banded import Banded, mask_band

__all__ = [
    "kp_coefficients",
    "kp_coefficient_rows",
    "gram_band_rows",
    "kp_factors",
    "gkp_factors",
    "phi_at",
    "phi_grad_at",
    "query_window_start",
]


def _kp_row_inputs(n, q: int, rows: jax.Array, clip_n: int | None = None):
    """Per-row window gather indices + Algorithm-2 category for ``rows``.

    Returns (window indices (r, 2q+3), validity, primary sign, aux sign,
    number of valid auxiliary equations) — everything ``_kp_build_rows`` needs,
    for an arbitrary subset of row indices (streaming updates rebuild only the
    O(q) window around an inserted point). ``n`` may be a *traced* active
    length (capacity padding) — it only enters comparisons; ``clip_n`` is the
    static allocation size to clip gather indices against (defaults to n).
    """
    t = jnp.arange(-(q + 1), q + 2)[None, :]
    j = rows[:, None] + t
    valid = (j >= 0) & (j < n)
    j_idx = jnp.clip(j, 0, (n if clip_n is None else clip_n) - 1)
    # row category: number of *valid* auxiliary equations and signs
    # left rows (i <= q): primary sign +1, aux sign -1, n_aux = i
    # central: both signs, all q+1 "aux" rows are the delta=-1 primary set
    # right rows (i >= n-q-1): primary sign -1, aux sign +1, n_aux = n-1-i
    is_left = rows <= q
    is_right = rows >= n - q - 1
    # For ties in tiny-n cases a row can be both; treat left first (matches Alg 2).
    primary_sign = jnp.where(is_left, 1.0, jnp.where(is_right, -1.0, 1.0))
    aux_sign = -primary_sign
    n_aux = jnp.where(is_left, rows, jnp.where(is_right, n - 1 - rows, q + 1))
    n_aux = jnp.minimum(n_aux, q + 1)
    return j_idx, valid, primary_sign, aux_sign, n_aux


def _null_vector(E):
    """Unit right null vectors of m x (m+1) matrices ``E`` (m, m+1, r).

    The last column of Q in a Householder QR of ``E^T``: ``E^T = Q R`` with
    R's last row zero gives ``E q = R^T Q^T q = 0`` at any rank, and the
    reflections are backward stable like an SVD null vector. The batch
    ``r`` trails, so every step is an elementwise op on lane-dense batch
    vectors (no tiny trailing matrix dims for the TPU's (8, 128) tiling to
    pad), and ``fori_loop``s over the m reflections keep the program one
    small loop body: under XLA's float64 emulation on the TPU an unrolled
    factorization costs minutes of compile.
    """
    m, P = E.shape[0], E.shape[1]
    idx = jnp.arange(P)[:, None]
    Et = jnp.swapaxes(E, 0, 1)  # (P, m, r)

    def reflect(k, carry):
        A, U = carry
        x = jnp.where(idx >= k, jax.lax.dynamic_index_in_dim(
            A, k, axis=1, keepdims=False), 0.0)
        xk = jax.lax.dynamic_index_in_dim(x, k, axis=0, keepdims=False)
        alpha = -jnp.where(xk >= 0, 1.0, -1.0) * jnp.sqrt(
            jnp.sum(x * x, axis=0))
        v = jnp.where(idx == k, x - alpha, x)
        vv = jnp.sum(v * v, axis=0)
        u = v * jnp.where(vv > 0, jnp.sqrt(2.0 / jnp.where(vv > 0, vv, 1.0)),
                          0.0)  # H_k = I - u u^T
        A = A - u[:, None] * jnp.sum(u[:, None] * A, axis=0)
        return A, jax.lax.dynamic_update_index_in_dim(U, u, k, axis=0)

    U = jnp.zeros((m,) + Et[:, 0].shape, E.dtype)
    _, U = jax.lax.fori_loop(0, m, reflect, (Et, U))

    def apply(i, y):  # q = H_0 ... H_{m-1} e_{P-1}
        u = jax.lax.dynamic_index_in_dim(U, m - 1 - i, axis=0, keepdims=False)
        return y - u * jnp.sum(u * y, axis=0)

    e_last = jnp.broadcast_to((idx == P - 1).astype(E.dtype), Et[:, 0].shape)
    return jax.lax.fori_loop(0, m, apply, e_last)


def _kp_build_rows(q: int, omega, xw, vw, psign, asign, naux):
    """KP coefficient rows from window points + Algorithm-2 categories.

    ``xw``/``vw``: (P, r) window points / validity with the row batch on the
    trailing axis; signs and ``naux``: (r,). Returns (r, P). The window
    system ``E a = 0`` (Eq. (9)) is formed entry by entry and solved for its
    null vector, all elementwise over the rows.
    """
    P = 2 * q + 3  # window size (central rows)
    dtype = xw.dtype
    # center & scale for conditioning (shift/scale invariance of Eq. (9))
    c = jnp.sum(jnp.where(vw, xw, 0.0), axis=0) / jnp.maximum(
        jnp.sum(vw, axis=0), 1)
    xt = jnp.where(vw, xw - c, 0.0)
    s = jnp.maximum(jnp.max(jnp.abs(xt), axis=0), 1e-30)
    xh = xt / s
    # column scaling to bound exp terms: factor exp(-omega |xt|)
    col_log = -omega * jnp.abs(xt)
    ep = jnp.exp(psign * omega * xt + col_log)  # (P, r)
    ea = jnp.exp(asign * omega * xt + col_log)
    # invalid columns pin a_p = 0: masked aux slot r (r >= naux) takes the
    # unit row of the invalid column whose rank among invalid ones is r-naux
    inv = ~vw
    inv_rank = jnp.clip(jnp.cumsum(inv, axis=0) - 1, 0, q)
    pw = [jnp.ones_like(xh)]
    for _ in range(q):
        pw.append(pw[-1] * xh)
    pw = jnp.stack(pw)  # (q+1, P, r): xh ** l
    slot = jnp.arange(q + 1)[:, None, None]
    live = slot < naux
    pin = ~live & inv & (inv_rank == slot - naux)
    E = jnp.concatenate([pw * ep, jnp.where(live, pw * ea, 0.0)
                         + pin.astype(dtype)])  # (2q+2, P, r)
    # a row that is both a left and a right row (n <= 2q+1) has more live
    # equations than free coefficients and all of K's row inside Phi's band:
    # it keeps the unit row e_i (every other coefficient pinned)
    degenerate = (q + 1 + naux >= jnp.sum(vw, axis=0)) & vw[q + 1]
    unit = np.delete(np.eye(P), q + 1, axis=0)[..., None]  # (2q+2, P, 1)
    E = jnp.where(degenerate, jnp.asarray(unit, dtype), E)
    a = _null_vector(E)  # (P, r)
    # undo column scaling
    a = jnp.where(vw, a * jnp.exp(col_log), 0.0)
    a = a / jnp.maximum(jnp.sqrt(jnp.sum(a * a, axis=0)), 1e-30)
    sign = jnp.sign(a[q + 1]) + (a[q + 1] == 0)
    return (a * sign).T


@partial(jax.jit, static_argnums=0)
@obs.scope("kp.build")
def kp_coefficient_rows(q: int, omega, xs: jax.Array, rows: jax.Array,
                        n_active=None) -> jax.Array:
    """KP coefficient rows (len(rows), 2q+3) for a subset of row indices.

    Each row is computed exactly as ``kp_coefficients`` would for the full
    matrix — streaming inserts use this to rebuild only the O(q) window of
    rows whose point windows (or boundary category) changed. Under capacity
    padding ``n_active`` (traced) is the logical matrix size: validity and
    the Algorithm-2 boundary category use it, and padded-tail ``xs`` values
    are masked out of the window math (they may hold anything).
    """
    n = xs.shape[0]
    na = n if n_active is None else n_active
    j_idx, valid, psign, asign, naux = _kp_row_inputs(na, q, rows, clip_n=n)
    xw = jnp.where(valid, xs[j_idx], 0.0)
    return _kp_build_rows(q, omega, xw.T, valid.T, psign, asign, naux)


@partial(jax.jit, static_argnums=0)
def kp_coefficients(q: int, omega, xs: jax.Array) -> Banded:
    """KP coefficient matrix A (half-bandwidths lo = hi = q+1).

    ``xs`` must be sorted ascending, shape (n,). Row i of A holds the
    coefficients a_j combining k(., x_j), j in window(i), into a compactly
    supported kernel packet (Thm 3). Rows are L2-normalized with the sign of
    the window-center coefficient fixed positive.
    """
    n = xs.shape[0]
    data = kp_coefficient_rows(q, omega, xs, jnp.arange(n))
    return mask_band(Banded(data, q + 1, q + 1))


@obs.scope("kp.build")
def gram_band_rows(kfun, xs: jax.Array, a_rows: jax.Array, rows: jax.Array,
                   loA: int, hiA: int, hw: int, n_active=None) -> jax.Array:
    """Rows of the band of Phi = A @ K restricted to ``rows``.

    ``a_rows`` are the matching coefficient rows of A (len(rows), loA+hiA+1);
    K[i, j] = kfun(xs[i], xs[j]). Row i only touches xs within
    i ± (max(loA, hiA) + hw), so a window rebuild is O(q) per row. Under
    capacity padding ``n_active`` (traced) bounds validity; out-of-range
    window points are zeroed *before* ``kfun`` so poisoned pad slots cannot
    produce NaNs that survive the mask.
    """
    n = xs.shape[0]
    na = n if n_active is None else n_active
    t = jnp.arange(-loA, hiA + 1)[None, :]
    j = rows[:, None] + t
    vv = (j >= 0) & (j < na)
    jj = jnp.clip(j, 0, n - 1)
    xw = jnp.where(vv, xs[jj], 0.0)  # (r, wA) points of each window
    m = jnp.arange(-hw, hw + 1)[None, :]
    jm_raw = rows[:, None] + m
    vm = (jm_raw >= 0) & (jm_raw < na)
    xm = jnp.where(vm, xs[jnp.clip(jm_raw, 0, n - 1)], 0.0)  # (r, wPhi)
    # phi[i, m] = sum_t A[i,t] k(x_{i+m}, x_{i+t})
    kv = kfun(xm[:, :, None], xw[:, None, :])  # (r, wPhi, wA)
    kv = kv * vv[:, None, :]
    data = jnp.einsum("nmt,nt->nm", kv, a_rows)
    return data * vm


def _phi_band_from_A(q: int, kfun, xs: jax.Array, A: Banded, hw: int) -> Banded:
    """Band of Phi = A @ K where K[i,j] = kfun(xs[i], xs[j]); half-bw ``hw``."""
    n = xs.shape[0]
    data = gram_band_rows(kfun, xs, A.data, jnp.arange(n), A.lo, A.hi, hw)
    return Banded(data, hw, hw)


@partial(jax.jit, static_argnums=0)
def kp_factors(q: int, omega, xs: jax.Array):
    """Algorithm 2: banded (A, Phi) with P^T K P = A^{-1} Phi (xs sorted)."""
    A = kp_coefficients(q, omega, xs)
    kfun = lambda x, y: mk.matern(q, omega, x, y)
    Phi = _phi_band_from_A(q, kfun, xs, A, q)
    return A, Phi


@partial(jax.jit, static_argnums=0)
def gkp_factors(q: int, omega, xs: jax.Array):
    """Algorithm 3: banded (B, Psi) with P^T [d_omega K] P = B^{-1} Psi.

    B is the Matérn-(nu+1) KP coefficient matrix on the same points (App. C).
    """
    B = kp_coefficients(q + 1, omega, xs)
    dkfun = lambda x, y: mk.matern_domega(q, omega, x, y)
    Psi = _phi_band_from_A(q + 1, dkfun, xs, B, q + 1)
    return B, Psi


def query_window_start(xs: jax.Array, xq: jax.Array,
                       n_active=None) -> jax.Array:
    """First KP row index with x* in its support: start = searchsorted - (q+1)...

    Returned *unclipped*; callers combine with validity masks. O(log n)
    unpadded. Under capacity padding (traced ``n_active``) the tail of ``xs``
    holds arbitrary values, so the insertion point is the masked count of
    active entries below ``xq`` — O(capacity) per query, identical to
    ``searchsorted(side="left")`` on the active prefix.
    """
    if n_active is None:
        return jnp.searchsorted(xs, xq, side="left")
    j = jnp.arange(xs.shape[0])
    lt = (xs < xq[..., None]) & (j < n_active)
    return jnp.sum(lt, axis=-1)


@partial(jax.jit, static_argnums=0)
@obs.scope("kp.windows")
def phi_at(q: int, omega, xs: jax.Array, A: Banded, xq: jax.Array,
           n_active=None):
    """Sparse KP vector phi(x*) = A k(X, x*): values + row indices.

    Returns (rows (..., 2q+2), vals (..., 2q+2), valid mask). At most
    2*nu+1 = 2q+2 consecutive rows are non-zero (Sec. 5.2). Under capacity
    padding (traced ``n_active``, defaulting to ``A.n_active``) validity is
    bounded by the active prefix and padded-tail points never enter the
    kernel evaluations.
    """
    if n_active is None:
        n_active = A.n_active
    n = xs.shape[0]
    na = n if n_active is None else n_active
    t = query_window_start(xs, xq, n_active=n_active)
    if t.ndim == 0:
        rows = t + jnp.arange(-(q + 1), q + 1)
    else:
        rows = t[..., None] + jnp.arange(-(q + 1), q + 1)
    valid = (rows >= 0) & (rows < na)
    # clamp into the ACTIVE prefix, not just the capacity: consumers gather
    # bY / Gband at these rows and multiply by the (zeroed) vals — a clamp to
    # a padded tail slot would turn stale/NaN tail contents into 0 * NaN
    rows_c = jnp.clip(rows, 0, jnp.maximum(na - 1, 0))
    # window points for each row: j = row + s, s in [-(q+1), q+1]
    s = jnp.arange(-(q + 1), q + 2)
    j = rows_c[..., None] + s
    jv = (j >= 0) & (j < na)
    jc = jnp.clip(j, 0, n - 1)
    xj = jnp.where(jv, xs[jc], 0.0)
    kv = mk.matern(q, omega, xj, xq[..., None, None]) * jv
    # (..., 2q+2, 2q+3); invalid rows may gather padded (arbitrary) slots —
    # zero them before the contraction so NaN poison cannot survive `* valid`
    avals = jnp.where(valid[..., None], A.data[rows_c], 0.0)
    vals = jnp.einsum("...rs,...rs->...r", avals, kv) * valid
    return rows_c, vals, valid


@partial(jax.jit, static_argnums=0)
@obs.scope("kp.windows")
def phi_grad_at(q: int, omega, xs: jax.Array, A: Banded, xq: jax.Array,
                n_active=None):
    """d phi(x*) / d x*: same sparsity pattern as phi_at."""
    if n_active is None:
        n_active = A.n_active
    n = xs.shape[0]
    na = n if n_active is None else n_active
    t = query_window_start(xs, xq, n_active=n_active)
    if t.ndim == 0:
        rows = t + jnp.arange(-(q + 1), q + 1)
    else:
        rows = t[..., None] + jnp.arange(-(q + 1), q + 1)
    valid = (rows >= 0) & (rows < na)
    rows_c = jnp.clip(rows, 0, jnp.maximum(na - 1, 0))  # active prefix (see phi_at)
    s = jnp.arange(-(q + 1), q + 2)
    j = rows_c[..., None] + s
    jv = (j >= 0) & (j < na)
    jc = jnp.clip(j, 0, n - 1)
    xj = jnp.where(jv, xs[jc], 0.0)
    dk = mk.matern_dx(q, omega, xq[..., None, None], xj) * jv
    avals = jnp.where(valid[..., None], A.data[rows_c], 0.0)
    vals = jnp.einsum("...rs,...rs->...r", avals, dk) * valid
    return rows_c, vals, valid
