"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(the kernel body executes in Python on CPU — exactly what the assignment
prescribes for validating TPU-target kernels without hardware)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import butterfly as bf
from repro.core import layers as bl
from repro.kernels import ops, ref
from repro.kernels.sandwich import one_hot_select


@pytest.mark.parametrize("n", [8, 64, 512, 128])
@pytest.mark.parametrize("batch", [1, 3, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_butterfly_kernel_forward(n, batch, dtype):
    w = bf.fjlt_weights(jax.random.PRNGKey(0), n)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, n)).astype(dtype)
    got = ops.butterfly_apply(x, w, context="pallas_interpret")
    want = ref.butterfly_ref(w.astype(dtype), x)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n", [16, 128, 256, 1024, 2048])
@pytest.mark.parametrize("transpose", [False, True])
def test_butterfly_kernel_transpose_and_grid(n, transpose):
    """Batch larger than one grid block exercises the BlockSpec tiling; from
    n = 128 on, the in-lane stages run as the folded MXU block (all of them
    at 128, all but one at 256)."""
    w = bf.random_weights(jax.random.PRNGKey(2), n)
    x = jax.random.normal(jax.random.PRNGKey(3), (700, n))
    got = ops.butterfly_apply(x, w, transpose=transpose,
                              context="pallas_interpret")
    want = ref.butterfly_ref(w, x, transpose=transpose)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_butterfly_kernel_nd_batch():
    n = 64
    w = bf.random_weights(jax.random.PRNGKey(4), n)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 3, 5, n))
    got = ops.butterfly_apply(x, w, context="pallas_interpret")
    want = ref.butterfly_ref(w, x)
    assert got.shape == x.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n1,n2,k1,k2", [(64, 64, 8, 8), (128, 256, 16, 12),
                                         (32, 128, 32, 16), (256, 128, 8, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sandwich_kernel_vs_layer(n1, n2, k1, k2, dtype):
    """Fused sandwich kernel == ButterflyLinear layer (the jnp production
    path) across shapes and dtypes."""
    spec = bl.make_spec(jax.random.PRNGKey(6), n1, n2, k_in=k1, k_out=k2,
                        use_bias=False)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(7), spec)
    x = jax.random.normal(jax.random.PRNGKey(8), (9, n1)).astype(dtype)
    want = bl.butterfly_linear_apply(spec, params, x)
    sel_in = one_hot_select(spec.idx_in, n1)
    sel_out = one_hot_select(spec.idx_out, n2).T
    got = ops.sandwich_apply(
        x, params["b_in"], sel_in, params["core"], sel_out, params["b_out"],
        scale_in=math.sqrt(n1 / k1), scale_out=math.sqrt(n2 / k2),
        context="pallas_interpret")
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_kernel_jnp_backend_matches_interpret():
    n = 128
    w = bf.fjlt_weights(jax.random.PRNGKey(9), n)
    x = jax.random.normal(jax.random.PRNGKey(10), (17, n))
    a = ops.butterfly_apply(x, w, context="jnp")
    b = ops.butterfly_apply(x, w, context="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_ref_matches_naive():
    """The flash oracle itself against a trivially-correct softmax."""
    B, H, S, D = 2, 3, 32, 16
    q = jax.random.normal(jax.random.PRNGKey(11), (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(12), (B, H, S, D))
    v = jax.random.normal(jax.random.PRNGKey(13), (B, H, S, D))
    out = ref.flash_attention_ref(q, k, v, causal=True)
    # naive
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.where(mask, logits, -1e30)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Flash-attention Pallas kernel (beyond-paper)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_oracle(causal, window, dtype):
    from repro.kernels.flash import flash_attention
    B, H, S, D = 2, 3, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(20), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, S, D)).astype(dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_kv=16, interpret=True)
    want = ref.flash_attention_ref(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32),
                                   causal=causal, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_kernel_block_shapes():
    from repro.kernels.flash import flash_attention
    B, H, S, D = 1, 2, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    for bq, bkv in [(32, 64), (64, 32), (128, 128)]:
        got = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_kv=bkv, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_nonlinear_butterfly_gates():
    """§7 future-work path: gated butterfly differs from linear, reduces to
    it when the activation is identity, and is differentiable."""
    from repro.core.butterfly import (butterfly_apply,
                                      butterfly_apply_nonlinear)
    n = 32
    w = bf.random_weights(jax.random.PRNGKey(22), n)
    x = jax.random.normal(jax.random.PRNGKey(23), (4, n))
    lin = butterfly_apply(w, x)
    gated = butterfly_apply_nonlinear(w, x)
    ident = butterfly_apply_nonlinear(w, x, act=lambda z: z)
    assert float(jnp.abs(gated - lin).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(ident), np.asarray(lin),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda w: jnp.sum(butterfly_apply_nonlinear(w, x) ** 2))(w)
    assert bool(jnp.isfinite(g).all())
