"""Windowed maintenance of the cached variance band ``Gband = (A Phi^T)^{-1}``.

The streaming mutations (`repro.streaming.updates`) change the per-dimension
KP system ``H = A Phi^T`` only inside an O(q) window of rows around the
insertion/eviction position ``p`` — every other row of the new factors is an
exact shifted copy of the old ones (Thm 3 locality, see ``_insert_dim``).
This module turns that locality into an exact *windowed* update of the
cached band of ``G = H^{-1}``, replacing the O(capacity)-sequential RGF
sweep (``band_inverse``) on the mutation path.

Why not splice the RGF ``F_j``/``W_j`` Schur complements directly: the RGF
block partition (blocks of width ``w``) misaligns under a one-row shift, so
cached forward/backward complements cannot be reused after a splice. What
*can* be carried across mutations is the band of ``H`` itself (``Hband`` on
:class:`~repro.core.additive_gp.AdditiveGP`): a row splice of a banded
matrix is a pure gather of band data, and the leftover perturbation is a
low-rank window term handled exactly by a Woodbury identity whose solves
are *banded* (log-depth block-CR on the pallas backend) rather than the
RGF's sequential block recursion.

The algebra (capacity-padded canonical form throughout — the padded matrix
is exactly ``blockdiag(H_active, I)``, see ``repro.masking``):

  * **Insert at sorted position p.** The padded canonical ``H_old`` has a
    decoupled identity slot at index ``k`` (the first pad row). Moving that
    slot to position ``p`` is a symmetric permutation ``H_s = P H_old P^T``
    that is *still banded* at half-bandwidth ``h + 1``: band entries gather
    from the old band with rows and columns shifted by one past ``p``
    (entries straddling ``p`` move one offset *outward*, so the spliced
    system is one offset wider than the stored band — the Woodbury solves
    and window block run at width ``h + 1``). The same permutation acts on
    the inverse, but the stored ``+-h`` band of ``G_s = P G_old P^T`` only
    *reads* offsets within ``+-h`` (for ``m > 0`` the source offset is
    ``m`` or ``m - 1``), so it stays a pure gather of the old ``Gband``.
    The true new system differs from ``H_s``
    only on the window rows ``|i - p| <= R`` (``R = 4q + 6``: factor
    rebuild radius ``2q + 4`` plus bandwidth ``2q + 1``, plus one row of
    safety), with columns within ``R + h`` of ``p``:

        H_new = H_s + E M F^T,      M = (H_new - H_s)[window rows, window cols]

    and Woodbury gives the exact new inverse

        G_new = G_s - G_s E (I + M F^T G_s E)^{-1} M F^T G_s.

    ``G_s E`` (window *columns* of the inverse) and ``F^T G_s`` (window
    *rows*) are two narrow banded solves against ``H_s`` / ``H_s^T``,
    evaluated on a fixed-size principal *patch* around ``p``
    (``patch_size`` rows — see the truncation paragraph below); on the jax
    backend both run as one stacked log-depth block-CR call
    (``kernels.cr_jax``). The small ``(r, r)`` system uses the same
    batch-invariant scan-LU as the RGF blocks
    (``band_inverse._block_solve``).

  * **Evict at sorted position p.** The evicted slot is *coupled*, so
    permuting it to the tail is not banded. Run the identity backwards
    instead: splice an identity slot at ``p`` into the already-computed
    ``H_new`` (banded gather again) to get ``H_s'``; then ``H_old = H_s' +
    E M F^T`` with the same window support, and

        G_s' = G_old + G_old E (I - M F^T G_old E)^{-1} M F^T G_old

    solves against the *cached* pre-mutation ``Hband``. Deleting row/column
    ``p`` from ``G_s'`` shifts straddling entries one offset *outward*, so
    the band of ``G_new`` needs ``2h`` entries of ``G_s'`` at offsets
    ``+-(h + 1)`` that the stored band lacks — but those rows/columns sit
    inside the solve windows, where the Woodbury gives *dense* rows
    (``F^T G_old`` plus correction) and columns (``G_old E`` plus
    correction), so they are reconstructed exactly.

**Truncation contract.** The Woodbury algebra above is exact, but the two
window solves run on a fixed-size principal submatrix (the *patch*,
``patch_size(q, C)`` rows centred on ``p``) instead of the full capacity,
and the band correction is written only to patch rows. Both approximations
drop terms that decay like the per-row state-transition factor
``exp(-omega * gap)`` away from ``p`` (banded-inverse off-diagonal decay —
the local Green's-function structure of the KP system), so with the
``TRUNC_MARGIN`` rows of slack the dropped mass is ~1e-16 relative in the
quasi-uniform streaming regime (``omega * gap >~ 0.3``) and the update is
*bit-exact* whenever the patch covers the whole capacity (every
test-scale problem). This is what makes the per-mutation solve cost
independent of capacity; the remaining O(capacity) terms — the new-``H``
band matmul and the splice gathers — are single fully-parallel
memory-bound ops. Densely oversampled data (``omega * gap -> 0``) has no
index-space decay: there the patch contract degrades and
``REPRO_GBAND=full`` (``kernels.ops.resolve_gband``) restores the exact
RGF sweep. Exactness is pinned against the full recompute to <= 1e-10
relative in ``tests/test_gband.py``, both with the patch covering the
matrix and with truncation active at fixed density. Repeated windowed
updates accumulate ordinary f64 roundoff (~1 ulp of correction per
mutation); extremely long streams that need the RGF's from-scratch
roundoff can pin ``REPRO_GBAND=full`` or refit.

Batch invariance: every contraction is an unrolled fixed-association loop
(``band_inverse._mm`` idiom) and the patch solves are built from the same
primitives (``kernels.cr_jax`` on jax, the dispatched solve on pallas), so
the update is bitwise invariant to the fleet lane count like the rest of
the mutation path.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import obs
from ..kernels import ops as _kops
from ..kernels.cr_jax import block_cr_solve_jax
from ..masking import canonical_band
from .band_inverse import _block_solve, _mm
from .banded import Banded, band_band_matmul, mask_band, solve, transpose

__all__ = ["gband_insert", "gband_evict", "window_radius"]


def window_radius(q: int) -> int:
    """Rows of ``H`` that an insert/evict can change around position ``p``.

    The factor rebuild window covers ``|i - p| <= 2q + 4``
    (``updates._insert_dim``); a row of ``H = A Phi^T`` mixes Phi rows
    within the bandwidth ``h = 2q + 1`` of it, and one extra row absorbs
    the tie-separation bump of the spliced coordinate.
    """
    return 4 * q + 6


def _window(p: jax.Array, R: int, C: int):
    """Clipped index window ``p - R .. p + R`` per dim: (idx, valid).

    Clipping creates duplicate indices at the boundaries; ``valid`` marks
    the in-range entries so duplicates are masked out of the low-rank term
    (a duplicated window row would otherwise be double-counted).
    """
    t = jnp.arange(2 * R + 1)
    u = p[:, None] - R + t[None, :]  # (D, 2R+1)
    valid = (u >= 0) & (u < C)
    return jnp.clip(u, 0, C - 1), valid


def _splice_band(data: jax.Array, h: int, p: jax.Array,
                 hout: int | None = None) -> jax.Array:
    """Band data (half-width ``hout >= h``) of ``P M P^T`` where ``P``
    inserts a decoupled slot at ``p``.

    ``data``: (D, C, 2h+1) band of a canonical padded matrix (the slot being
    moved in is an identity pad row). Rows/columns past ``p`` shift down by
    one; entries straddling ``p`` (row side and column side shifting by
    different amounts) move one offset *outward*, so the spliced matrix has
    half-bandwidth ``h + 1`` — callers that need it exactly (the ``H``
    splices feeding the Woodbury solves) pass ``hout = h + 1``; the ``G``
    splices only read the stored ``+-h`` band, whose sources always stay in
    band (for ``m > 0`` the source offset is ``m`` or ``m - 1``, mirrored
    for ``m < 0``). Row/column ``p`` become the decoupled identity slot.
    """
    if hout is None:
        hout = h
    D, C, W = data.shape
    i = jnp.arange(C)[None, :, None]
    m = jnp.arange(-hout, hout + 1)[None, None, :]
    j = i + m
    pp = p[:, None, None]
    src_i = jnp.clip(i - (i > pp), 0, C - 1)  # (D, C, 1)
    src_j = j - (j > pp)
    src_m = src_j - src_i  # m or m -+ 1
    d = jnp.arange(D)[:, None, None]
    val = data[d, src_i, jnp.clip(h + src_m, 0, W - 1)]
    val = jnp.where((src_m >= -h) & (src_m <= h), val, 0.0)
    ident = jnp.where((i == pp) & (m == 0), 1.0, 0.0).astype(data.dtype)
    val = jnp.where((i == pp) | (j == pp), ident, val)
    return jnp.where((j >= 0) & (j < C), val, 0.0)


def _widen(data: jax.Array, dh: int) -> jax.Array:
    """Pad band data (D, C, W) with ``dh`` zero offsets on each side."""
    return jnp.pad(data, ((0, 0), (0, 0), (dh, dh)))


def _onehot_cols(idx: jax.Array, valid: jax.Array, C: int, dtype) -> jax.Array:
    """(D, r) window indices -> (D, C, r) one-hot RHS columns, invalid ones 0."""
    D, r = idx.shape
    d = jnp.arange(D)[:, None]
    t = jnp.arange(r)[None, :]
    vals = jnp.where(valid, 1.0, 0.0).astype(dtype)
    return jnp.zeros((D, C, r), dtype).at[d, idx, t].set(vals)


def _window_block(delta: jax.Array, h: int, wr, vr, wc, vc) -> jax.Array:
    """M = delta[window rows, window cols] with duplicate/invalid masking."""
    W = delta.shape[-1]
    off = wc[:, None, :] - wr[:, :, None]  # (D, r, c)
    inband = (off >= -h) & (off <= h)
    d = jnp.arange(delta.shape[0])[:, None, None]
    vals = delta[d, wr[:, :, None], jnp.clip(h + off, 0, W - 1)]
    keep = inband & vr[:, :, None] & vc[:, None, :]
    return jnp.where(keep, vals, 0.0)


def _low_rank_band(X: jax.Array, V: jax.Array, h: int) -> jax.Array:
    """Band (|offset| <= h) of ``X @ V``: out[d, i, m] = sum_t X[d,i,t] V[d,t,i+m].

    Unrolled fixed-association t-loop (static window size), one gathered
    (D, C, 2h+1) term at a time — bitwise batch-invariant and O(C r h).
    """
    C, r = X.shape[1], X.shape[2]
    i = jnp.arange(C)[:, None]
    m = jnp.arange(-h, h + 1)[None, :]
    j = i + m
    jc = jnp.clip(j, 0, C - 1)
    out = X[:, :, 0, None] * V[:, 0][:, jc]
    for t in range(1, r):
        out = out + X[:, :, t, None] * V[:, t][:, jc]
    return jnp.where((j >= 0) & (j < C), out, 0.0)


def _new_hband(A: Banded, Phi: Banded, k_new, backend: str | None) -> jax.Array:
    """Canonical band data of the post-mutation ``H = A Phi^T``.

    One O(C h^2) fully-parallel band-band matmul — the rows outside the
    factor rebuild window are products of bitwise-identical factor rows, so
    they reproduce the spliced old band bit-for-bit (which is what makes
    the window perturbation exactly window-supported).
    """
    H = mask_band(band_band_matmul(A, transpose(Phi), backend=backend))
    return canonical_band(H.data, H.lo, H.hi, k_new)


TRUNC_MARGIN = 112
"""Patch rows kept on each side *beyond* the perturbation window.

The patch principal-submatrix solve agrees with the global solve up to
boundary terms that decay like the state-transition factor
``exp(-omega * gap)`` per row; over the margin the residual is
``exp(-sum of omega * gap)`` — ~1e-16 relative at ``omega * gap >= 0.32``
(the quasi-uniform streaming regime), comfortably inside the 1e-10
contract for ``omega * gap >= 0.21``. Densely oversampled data (tiny
``omega * gap``) has no index-space decay and breaks the contract; under
``config.health == "on"`` the per-mutation :func:`_drift_estimate` detects
the non-decay and the streaming sentinel (``updates.maybe_resync``)
replaces the bad band with an exact full-RGF recompute automatically —
``REPRO_GBAND=full`` remains the manual escape hatch for health-off runs.
"""


def patch_size(q: int, C: int) -> int:
    """Static patch length for the truncated window solves (min with C)."""
    L = window_radius(q) + (2 * q + 2) + TRUNC_MARGIN
    return min(C, 2 * L + 1)


def _gather_patch(data: jax.Array, ps: jax.Array, P: int,
                  h: int) -> jax.Array:
    """Principal submatrix rows ``ps .. ps+P-1`` of a (D, C, 2h+1) band.

    Band entries whose column leaves the patch are dropped — that is the
    truncation (the dropped couplings re-enter only through the decaying
    boundary terms the margin absorbs).
    """
    D = data.shape[0]
    i = jnp.arange(P)[None, :]
    rows = ps[:, None] + i  # (D, P); always in-matrix by construction
    d = jnp.arange(D)[:, None]
    patch = data[d, rows]  # (D, P, 2h+1)
    jl = i[:, :, None] + jnp.arange(-h, h + 1)[None, None, :]
    return jnp.where((jl >= 0) & (jl < P), patch, 0.0)


def _solve_windows(Hdata: jax.Array, hs: int, E: jax.Array, F: jax.Array,
                   backend: str | None, alg: str | None):
    """Patch columns ``X = H^{-1} E`` and rows ``Y^T = (H^{-T} F)^T``.

    Two narrow banded solves (pivoted — same robustness class as the RGF's
    pivoted block solves) over the fixed-size patch. ``hs`` is the
    half-bandwidth of ``Hdata`` (``h + 1`` for the spliced insert system).

    Both backends run the H and H^T systems as ONE stacked call with the
    transposed system on a leading batch axis — the RHS are zero-padded to
    a common column count and the outputs sliced back. On "jax" that is
    the pure-JAX compacted block-CR (``kernels.cr_jax``): log-depth
    vectorized levels instead of the scan-LU's P *sequential* steps. On
    "pallas" the stacked batch folds into the kernel grid
    (``kernels.ops._flatten_batch``), so the pair costs one ``pallas_call``
    instead of two dispatches. Stacking is bit-neutral on both paths: each
    grid entry / batch lane solves its system independently and the
    column-wise small-solves never mix RHS columns, so the stacked results
    are bitwise equal to two separate calls (pinned in
    ``tests/test_health.py``). This opt-in is local to the Gband window
    solves — the global ``banded_solve`` dispatch is untouched, so no
    other call site changes numerics.
    """
    Hb = Banded(Hdata, hs, hs)
    r, c = E.shape[-1], F.shape[-1]
    w = max(r, c)
    Ep = jnp.pad(E, ((0, 0), (0, 0), (0, w - r)))
    Fp = jnp.pad(F, ((0, 0), (0, 0), (0, w - c)))
    Hpair = jnp.stack([Hdata, transpose(Hb).data])
    rhs = jnp.stack([Ep, Fp])
    if _kops.resolve_backend(backend) == "jax":
        out = block_cr_solve_jax(Hpair, rhs, hs)
    else:
        out = solve(Banded(Hpair, hs, hs), rhs, pivot=True, backend=backend,
                    alg=alg)
    X, Y = out[0][..., :r], out[1][..., :c]
    return X, jnp.swapaxes(Y, 1, 2)


def _woodbury(Hsolve: jax.Array, hs: int, delta: jax.Array, hd: int,
              p: jax.Array, q: int, sign: float, backend: str | None,
              alg: str | None):
    """Shared window Woodbury: X, V with ``correction = sign * X @ V``.

    ``(H + E M F^T)^{-1} = H^{-1} - X (I + M F^T X)^{-1} M Y^T`` with
    ``X = H^{-1} E``, ``Y^T = F^T H^{-1}``; ``sign=-1`` is the insert
    direction (perturb ``H_s`` forward), ``sign=+1`` the evict direction
    (``H_old = H_s' + E M F^T`` solved backwards, flipping the Schur sign).
    ``Hsolve`` has half-bandwidth ``hs``; ``delta`` half-bandwidth ``hd``
    (``h + 1``: the splice's outward-moving straddles live at ``+-(h+1)``).

    The solves run on the fixed-size principal patch around ``p``
    (``patch_size`` rows), so the Schur/solve work per mutation is
    independent of the capacity; ``X``/``Yt``/``V`` are patch-indexed and
    ``ps`` maps them back to global rows. When the patch covers the whole
    matrix (every test-scale capacity) the update is exact.
    """
    C = Hsolve.shape[1]
    R = window_radius(q)
    P = patch_size(q, C)
    ps = jnp.clip(p - (P - 1) // 2, 0, C - P)  # (D,) patch start
    wr, vr = _window(p, R, C)
    wc, vc = _window(p, R + hd, C)
    M = _window_block(delta, hd, wr, vr, wc, vc)  # (D, r, c)
    Hp = _gather_patch(Hsolve, ps, P, hs)
    E = _onehot_cols(wr - ps[:, None], vr, P, Hsolve.dtype)
    F = _onehot_cols(wc - ps[:, None], vc, P, Hsolve.dtype)
    X, Yt = _solve_windows(Hp, hs, E, F, backend, alg)
    X_wc = jnp.take_along_axis(X, (wc - ps[:, None])[:, :, None], axis=1)
    r = M.shape[1]
    eye = jnp.eye(r, dtype=Hsolve.dtype)
    S = eye - sign * _mm(M, X_wc)  # (D, r, r); invalid rows stay e_t
    V = _block_solve(S, _mm(M, Yt))  # (D, r, P)
    return X, V, Yt, wr, wc, ps


DRIFT_EDGE = 8
"""Patch-edge rows sampled by the truncation-drift estimator."""


def _drift_estimate(corr: jax.Array, ps: jax.Array, k_new,
                    gscale: jax.Array) -> jax.Array:
    """Per-mutation check of the truncation's decay contract.

    The patch truncation is valid exactly when the Woodbury correction has
    decayed (at its ``exp(-omega * gap)`` rate) to roundoff by the patch
    boundary: that same decay bounds both the dropped tail *and* the
    boundary terms that make the truncated patch solve agree with the
    global one. So the signal is the correction magnitude on the
    outermost ``DRIFT_EDGE`` patch rows **relative to the correction's own
    peak**: a correction that has not died off by the boundary means the
    no-decay regime, where the patch solve itself is untrustworthy (the
    interior error can exceed the edge magnitude by orders — dense
    oversampling produces exactly this). The normalizer is
    ``min(peak, gscale)`` per dimension: when the correction is larger
    than the band itself, ``edge / gscale`` is the band-relative error and
    is the bigger (still conservative) ratio. Each side counts only when
    truncation is actually active there (left: ``ps > 0``; right: the
    patch ends before the active prefix does), so the estimate is
    *exactly zero* whenever the patch covers the active system and the
    update is exact. The sentinel accumulates it across mutations
    (``HealthState.drift``) and triggers an exact full-RGF resync past
    ``health.verdict.DRIFT_TOL``.
    """
    P = corr.shape[1]
    e = min(DRIFT_EDGE, P)
    absc = jnp.abs(corr)
    left = jnp.max(absc[:, :e], axis=(1, 2))  # (D,)
    right = jnp.max(absc[:, P - e:], axis=(1, 2))
    edge = jnp.maximum(jnp.where(ps > 0, left, 0.0),
                       jnp.where(ps + P < k_new, right, 0.0))
    peak = jnp.max(absc, axis=(1, 2))  # (D,)
    tiny = jnp.asarray(jnp.finfo(corr.dtype).tiny, corr.dtype)
    scale = jnp.maximum(jnp.minimum(peak, gscale), tiny)
    return jnp.max(edge / scale)


def _add_patch_band(Gdata: jax.Array, corr: jax.Array,
                    ps: jax.Array) -> jax.Array:
    """Scatter-add the patch-local band correction into the full band."""
    D, P = corr.shape[0], corr.shape[1]
    d = jnp.arange(D)[:, None]
    rows = ps[:, None] + jnp.arange(P)[None, :]
    return Gdata.at[d, rows].add(corr)


@obs.scope("gband.update")
def gband_insert(Hband_old: Banded, A: Banded, Phi: Banded,
                 Gband_old: Banded, p: jax.Array, k_new, q: int, *,
                 backend: str | None = None,
                 alg: str | None = None) -> tuple[Banded, Banded]:
    """Windowed (Gband, Hband) after inserting at sorted positions ``p``.

    ``Hband_old``/``Gband_old``: the pre-insert cached bands (canonical,
    (D, C, 2h+1)); ``A``/``Phi``: the post-insert spliced factors;
    ``p``: (D,) per-dimension sorted insert position; ``k_new``: traced new
    active count. Returns ``(Gband, Hband, drift)``: the post-insert bands
    — active-prefix equal to the full RGF recompute up to roundoff plus
    the exponentially small patch truncation (exact whenever the patch
    covers the capacity) — and the scalar :func:`_drift_estimate` of this
    mutation's truncated tail for the health sentinel.
    """
    h = A.lo + Phi.lo  # 2q + 1
    # the spliced system has half-bandwidth h + 1 (outward straddles)
    Hs = _splice_band(Hband_old.canonical().data, h, p, hout=h + 1)
    Hnew = _new_hband(A, Phi, k_new, backend)
    delta = _widen(Hnew, 1) - Hs
    X, V, _, _, _, ps = _woodbury(Hs, h + 1, delta, h + 1, p, q, -1.0,
                                  backend, alg)
    Gs = _splice_band(Gband_old.canonical().data, h, p)
    corr = _low_rank_band(X, V, h)
    drift = _drift_estimate(corr, ps, k_new, jnp.max(jnp.abs(Gs)))
    Gnew = _add_patch_band(Gs, -corr, ps)
    Gnew = canonical_band(Gnew, h, h, k_new)
    return (Banded(Gnew, h, h, k_new), Banded(Hnew, h, h, k_new), drift)


@obs.scope("gband.update")
def gband_evict(Hband_old: Banded, A: Banded, Phi: Banded,
                Gband_old: Banded, p: jax.Array, k_new, q: int, *,
                backend: str | None = None,
                alg: str | None = None) -> tuple[Banded, Banded]:
    """Windowed (Gband, Hband) after evicting sorted positions ``p``.

    Arguments mirror :func:`gband_insert` (``A``/``Phi`` are the
    post-evict factors, ``k_new`` the decremented active count); the solves
    run against the *cached* pre-evict ``Hband_old``. Returns
    ``(Gband, Hband, drift)`` like :func:`gband_insert`.
    """
    h = A.lo + Phi.lo
    C = Hband_old.data.shape[1]
    W = 2 * h + 1
    D = Hband_old.data.shape[0]
    Hold = Hband_old.canonical().data
    Hnew = _new_hband(A, Phi, k_new, backend)
    # identity slot respliced at p; half-bandwidth h + 1 (outward straddles)
    Hs = _splice_band(Hnew, h, p, hout=h + 1)
    delta = _widen(Hold, 1) - Hs
    X, V, Yt, wr, wc, pstart = _woodbury(Hold, h, delta, h + 1, p, q, 1.0,
                                         backend, alg)
    # G_s' = G_old + X V on the stored band ...
    Gold = Gband_old.canonical().data
    corr = _low_rank_band(X, V, h)
    drift = _drift_estimate(corr, pstart, k_new, jnp.max(jnp.abs(Gold)))
    Gs = _add_patch_band(Gold, corr, pstart)

    # ... plus the 2h entries at offsets +-(h+1) that deleting row/column p
    # shifts into the band. Both sit inside the solve windows: rows
    # p-h..p-1 of G_s' are Yt rows + correction (a = p-h+s lands at window
    # slot (R+h+1)+(a-p) = R+1+s of the radius-(R+h+1) wc window), columns
    # p-h..p-1 are X columns + correction (slot R-h+s of the radius-R wr
    # window); out-of-range cases are masked by the final canonicalization,
    # so the clipped indices never leak.
    R = window_radius(q)
    P = X.shape[1]
    d = jnp.arange(D)[:, None]
    s = jnp.arange(h)[None, :]
    r_all = V.shape[1]

    def _loc(idx):
        # global rows/cols near p -> patch-local (always in the patch)
        return jnp.clip(idx - pstart[:, None], 0, P - 1)

    def _dense_entries(base, rows, cols):
        # G_s'[rows, cols] = G_old[rows, cols] + sum_t X[rows, t] V[t, cols]
        out = base
        for t in range(r_all):
            out = out + X[d, _loc(rows), t] * V[d, t, _loc(cols)]
        return out

    # upper straddle: G_s'[a, a + h + 1] for a = p-h .. p-1
    rows_up = jnp.clip(p[:, None] - h + s, 0, C - 1)
    cols_up = jnp.clip(p[:, None] + 1 + s, 0, C - 1)
    upper = _dense_entries(Yt[d, R + 1 + s, _loc(cols_up)], rows_up, cols_up)
    # lower straddle: G_s'[c + h + 1, c] for c = p-h .. p-1
    rows_lo = jnp.clip(p[:, None] + 1 + s, 0, C - 1)
    cols_lo = jnp.clip(p[:, None] - h + s, 0, C - 1)
    lower = _dense_entries(X[d, _loc(rows_lo), R - h + s], rows_lo, cols_lo)

    # delete row/column p: rows/cols past p shift up, straddling entries
    # move one offset outward (the +-(h+1) cases read upper/lower)
    i = jnp.arange(C)[None, :, None]
    m = jnp.arange(-h, h + 1)[None, None, :]
    j = i + m
    pp = p[:, None, None]
    src_i = jnp.clip(i + (i >= pp), 0, C - 1)
    src_j = j + (j >= pp)
    src_m = src_j - src_i
    dd = jnp.arange(D)[:, None, None]
    val = Gs[dd, src_i, jnp.clip(h + src_m, 0, W - 1)]
    up_case = (m == h) & (i < pp) & (j >= pp)
    lo_case = (m == -h) & (j < pp) & (i >= pp)
    i2 = jnp.broadcast_to(i[..., 0], (D, C))
    p2 = pp[..., 0]
    up_vals = jnp.take_along_axis(
        upper, jnp.clip(i2 - p2 + h, 0, h - 1), axis=1)[:, :, None]
    lo_vals = jnp.take_along_axis(
        lower, jnp.clip(i2 - p2, 0, h - 1), axis=1)[:, :, None]
    val = jnp.where(up_case, up_vals, val)
    val = jnp.where(lo_case, lo_vals, val)
    val = jnp.where((j >= 0) & (j < C), val, 0.0)
    Gnew = canonical_band(val, h, h, k_new)
    return (Banded(Gnew, h, h, k_new), Banded(Hnew, h, h, k_new), drift)
