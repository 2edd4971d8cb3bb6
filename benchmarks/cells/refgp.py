"""Plain reference: the dense additive Matérn GP, on the host CPU in float64.

It imports nothing of the system under test and takes nothing it made: the
data comes from the harness's own generator. Paper Eqs. (1)-(2): K is the
sum over dimensions of half-integer Matérn kernels (Eq. (37) with
nu = q + 1/2, unit amplitude), plus sigma^2 I, factored by Cholesky.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.linalg as sla

_BLOCK = 512  # gram rows per thread task
_THREADS = max(1, min(16, os.cpu_count() or 1))


def _coeffs(q: int) -> np.ndarray:
    """c_m of (2 omega r)^m, m = 0..q, in Eq. (37)."""
    pref = math.factorial(q) / math.factorial(2 * q)
    c = np.zeros(q + 1)
    for l in range(q + 1):
        c[q - l] = pref * math.factorial(q + l) / (
            math.factorial(l) * math.factorial(q - l))
    return c


def matern(q: int, om: float, r: np.ndarray) -> np.ndarray:
    """k(r) = exp(-om r) sum_m c_m (2 om r)^m, r = |x - x'|."""
    c = _coeffs(q)
    z = 2.0 * om * r
    return np.exp(-om * r) * np.polynomial.polynomial.polyval(z, c)


def matern_dr(q: int, om: float, r: np.ndarray) -> np.ndarray:
    """dk/dr."""
    c = _coeffs(q)
    z = 2.0 * om * r
    dc = np.polynomial.polynomial.polyder(c) if q > 0 else np.zeros(1)
    return np.exp(-om * r) * (-om * np.polynomial.polynomial.polyval(z, c)
                              + 2.0 * om * np.polynomial.polynomial.polyval(z, dc))


def gram(q: int, omega: np.ndarray, A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """K[i, j] = sum_d k_d(A[i, d], B[j, d]), built in row blocks on threads
    (NumPy's ufuncs release the interpreter lock)."""
    K = np.empty((A.shape[0], B.shape[0]))

    def block(i0):
        rows = slice(i0, i0 + _BLOCK)
        acc = K[rows]
        acc[...] = 0.0
        r = np.empty_like(acc)
        for d in range(A.shape[1]):
            np.subtract(A[rows, d, None], B[None, :, d], out=r)
            np.abs(r, out=r)
            if q == 0:  # exp(-om r), in place
                r *= -omega[d]
                np.exp(r, out=r)
                acc += r
            else:
                acc += matern(q, omega[d], r)

    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(block, range(0, A.shape[0], _BLOCK)))
    return K


class DenseGP:
    """Posterior of the additive GP on (X, Y) by one dense Cholesky."""

    def __init__(self, q: int, omega, sigma: float, X, Y):
        self.q, self.omega, self.sigma = q, np.asarray(omega, float), float(sigma)
        self.X, self.Y = np.asarray(X, float), np.asarray(Y, float)
        K = gram(q, self.omega, self.X, self.X)
        K[np.diag_indices_from(K)] += self.sigma ** 2
        self.cho = sla.cho_factor(K, lower=True, overwrite_a=True,
                                  check_finite=False)
        self.alpha = sla.cho_solve(self.cho, self.Y, check_finite=False)

    def log_marginal(self) -> float:
        """log p(Y) = -(Y^T K^-1 Y + log|K| + n log 2 pi) / 2 (paper Eq.
        (14)), log|K| from the Cholesky factor's diagonal."""
        n = self.Y.shape[0]
        logdet = 2.0 * float(np.sum(np.log(np.diag(self.cho[0]))))
        return -0.5 * (float(self.Y @ self.alpha) + logdet
                       + n * math.log(2.0 * math.pi))

    def prior_var(self) -> float:
        return float(sum(matern(self.q, om, np.zeros(1))[0] for om in self.omega))

    def mean_var(self, Xq):
        kq = gram(self.q, self.omega, self.X, np.asarray(Xq, float))  # (n, m)
        v = sla.solve_triangular(self.cho[0], kq, lower=True, check_finite=False)
        return kq.T @ self.alpha, self.prior_var() - np.sum(v * v, axis=0)

    def ucb(self, Xq, beta: float):
        """GP-UCB value mu + beta sqrt(var) and its gradient in x."""
        Xq = np.asarray(Xq, float)
        kq = gram(self.q, self.omega, self.X, Xq)
        w = sla.cho_solve(self.cho, kq, check_finite=False)  # K^-1 k(X, x)
        mu = kq.T @ self.alpha
        var = np.maximum(self.prior_var() - np.sum(kq * w, axis=0), 1e-12)
        dmu = np.empty_like(Xq)
        dvar = np.empty_like(Xq)
        for d in range(Xq.shape[1]):
            diff = Xq[None, :, d] - self.X[:, d, None]  # (n, m)
            dk = matern_dr(self.q, self.omega[d], np.abs(diff)) * np.sign(diff)
            dmu[:, d] = dk.T @ self.alpha
            dvar[:, d] = -2.0 * np.sum(dk * w, axis=0)
        s = np.sqrt(var)
        return mu + beta * s, dmu + (beta / (2.0 * s))[:, None] * dvar
