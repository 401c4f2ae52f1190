"""Core butterfly math: materialization, transpose, FJLT, param counts,
and hypothesis property tests on the network invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import butterfly as bf


@pytest.mark.parametrize("n", [2, 8, 64, 256])
def test_apply_matches_materialized(n):
    w = bf.random_weights(jax.random.PRNGKey(0), n)
    B = np.asarray(bf.materialize(w))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (5, n)))
    got = np.asarray(bf.butterfly_apply(w, jnp.asarray(x)))
    np.testing.assert_allclose(got, x @ B.T, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [4, 32, 128])
def test_transpose_matches_materialized(n):
    w = bf.random_weights(jax.random.PRNGKey(2), n)
    B = np.asarray(bf.materialize(w))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (3, n)))
    got = np.asarray(bf.butterfly_transpose_apply(w, jnp.asarray(x)))
    np.testing.assert_allclose(got, x @ B, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [16, 64, 1024])
def test_fjlt_is_orthogonal(n):
    w = bf.fjlt_weights(jax.random.PRNGKey(4), n)
    B = np.asarray(bf.materialize(w))
    np.testing.assert_allclose(B @ B.T, np.eye(n), atol=1e-5)


def test_fjlt_norm_preservation():
    n = 512
    w = bf.fjlt_weights(jax.random.PRNGKey(5), n)
    x = jax.random.normal(jax.random.PRNGKey(6), (20, n))
    y = bf.butterfly_apply(w, x)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=1),
                               np.linalg.norm(np.asarray(x), axis=1),
                               rtol=1e-5)


def test_truncation_jl_isometry_in_expectation():
    """sqrt(n/ell)-scaled coordinate sampling of the FJLT preserves norms in
    expectation (the JL property the paper builds on)."""
    n, ell, trials = 256, 64, 50
    x = np.array(jax.random.normal(jax.random.PRNGKey(7), (n,)))
    x = x / np.linalg.norm(x)
    norms = []
    for t in range(trials):
        kw, ki = jax.random.split(jax.random.PRNGKey(100 + t))
        w = bf.fjlt_weights(kw, n)
        idx = bf.truncation_indices(ki, n, ell)
        y = bf.truncate(bf.butterfly_apply(w, jnp.asarray(x)), idx, n)
        norms.append(float(jnp.sum(y * y)))
    assert abs(np.mean(norms) - 1.0) < 0.15


def test_effective_param_count_bound():
    for n in (64, 256, 1024):
        for ell in (4, 16, n // 4):
            idx = list(range(ell))
            exact = bf.effective_param_count(n, idx)
            assert exact <= bf.effective_param_bound(n, ell)


def test_truncate_untruncate_adjoint():
    """<truncate(x), y> == <x, untruncate(y)> (adjointness incl. JL scale)."""
    n, ell = 64, 16
    idx = bf.truncation_indices(jax.random.PRNGKey(8), n, ell)
    x = jax.random.normal(jax.random.PRNGKey(9), (n,))
    y = jax.random.normal(jax.random.PRNGKey(10), (ell,))
    lhs = jnp.vdot(bf.truncate(x, idx, n), y)
    rhs = jnp.vdot(x, bf.untruncate(y, idx, n))
    np.testing.assert_allclose(float(lhs), float(rhs), rtol=1e-5)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(logn=st.integers(1, 7), seed=st.integers(0, 2**30))
def test_property_linearity(logn, seed):
    n = 1 << logn
    w = bf.random_weights(jax.random.PRNGKey(seed), n)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(k1, (n,))
    y = jax.random.normal(k2, (n,))
    a = 2.5
    lhs = bf.butterfly_apply(w, a * x + y)
    rhs = a * bf.butterfly_apply(w, x) + bf.butterfly_apply(w, y)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=2e-3, atol=2e-3)


@settings(max_examples=20, deadline=None)
@given(logn=st.integers(1, 6), seed=st.integers(0, 2**30))
def test_property_transpose_adjoint(logn, seed):
    """<Bx, y> == <x, Bᵀy> for random weights — validates the transposed
    stage formula used by the sandwich's output butterfly."""
    n = 1 << logn
    w = bf.random_weights(jax.random.PRNGKey(seed), n)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 7))
    x = jax.random.normal(k1, (n,))
    y = jax.random.normal(k2, (n,))
    lhs = float(jnp.vdot(bf.butterfly_apply(w, x), y))
    rhs = float(jnp.vdot(x, bf.butterfly_transpose_apply(w, y)))
    np.testing.assert_allclose(lhs, rhs, rtol=5e-3, atol=5e-3)


@settings(max_examples=15, deadline=None)
@given(logn=st.integers(2, 6), seed=st.integers(0, 2**30))
def test_property_identity_weights(logn, seed):
    n = 1 << logn
    w = bf.identity_weights(n)
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, n))
    np.testing.assert_allclose(np.asarray(bf.butterfly_apply(w, x)),
                               np.asarray(x), atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(logn=st.integers(1, 5), seed=st.integers(0, 2**30),
       stride_pow=st.integers(0, 4))
def test_property_swap_involution(logn, seed, stride_pow):
    n = 1 << logn
    stride = 1 << min(stride_pow, logn - 1)
    if 2 * stride > n:
        stride = n // 2
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    y = bf.stage_swap(bf.stage_swap(x, stride), stride)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0)
