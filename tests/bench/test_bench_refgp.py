"""The plain reference (``refgp.py``, NumPy float64) against the program's
own dense oracle (``core/exact.py``): the same posterior and the same log
marginal likelihood on the same data."""
import numpy as np
import pytest

import benchtiny  # noqa: F401  (puts the harness on the path)
import refgp


@pytest.mark.parametrize("q", [0, 1])
def test_dense_gp_matches_the_exact_oracle(q):
    import jax.numpy as jnp

    from repro.core import exact

    g = np.random.default_rng(16 + q)
    n, D = 256, 3
    X = g.uniform(-2.0, 2.0, size=(n, D))
    Y = np.sin(X).sum(axis=1) + 0.3 * g.standard_normal(n)
    Xq = g.uniform(-2.0, 2.0, size=(7, D))
    omega, sigma = np.array([0.7, 1.3, 2.1]), 0.4
    ref = refgp.DenseGP(q, omega, sigma, X, Y)
    want = float(exact.log_marginal_likelihood(
        q, jnp.asarray(omega), sigma, jnp.asarray(X), jnp.asarray(Y)))
    assert ref.log_marginal() == pytest.approx(want, rel=1e-10, abs=1e-8)
    mu, var = ref.mean_var(Xq)
    mu_x, var_x = exact.posterior_mean_var(
        q, jnp.asarray(omega), sigma, jnp.asarray(X), jnp.asarray(Y),
        jnp.asarray(Xq))
    np.testing.assert_allclose(mu, np.asarray(mu_x), rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(var, np.asarray(var_x), rtol=1e-8, atol=1e-10)
