"""Gradients through the fused Pallas kernels (custom_vjp backward passes).

``jax.grad`` through ``butterfly_apply`` / ``sandwich_apply`` under
``context="pallas_interpret"`` must match the jnp-oracle gradients — input
*and* weight cotangents, forward and transpose variants — to atol 1e-5.
The interpret backend executes the exact backward kernel bodies (grid
accumulation included) in Python on CPU, which is what validates the
TPU-target kernels without hardware.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import butterfly as bf
from repro.core import layers as bl
from repro.kernels import context as exctx
from repro.kernels import ops, ref, tuning
from repro.kernels import butterfly as bkern
from repro.kernels.butterfly import butterfly_matmul, count_stage_applies
from repro.kernels.sandwich import one_hot_select, sandwich_matmul


def _assert_close(got, want, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=atol)


# ---------------------------------------------------------------------------
# Butterfly VJP vs oracle autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [8, 64, 256, 128, 1024, 2048])
@pytest.mark.parametrize("transpose", [False, True])
def test_butterfly_grad_matches_oracle(n, transpose):
    w = bf.random_weights(jax.random.PRNGKey(0), n)
    x = jax.random.normal(jax.random.PRNGKey(1), (11, n))
    c = jax.random.normal(jax.random.PRNGKey(2), (11, n))

    def loss(backend):
        return lambda x, w: jnp.vdot(c, ops.butterfly_apply(
            x, w, transpose=transpose, context=backend))

    gx_k, gw_k = jax.grad(loss("pallas_interpret"), argnums=(0, 1))(x, w)
    gx_o, gw_o = jax.grad(loss("jnp"), argnums=(0, 1))(x, w)
    _assert_close(gx_k, gx_o)
    _assert_close(gw_k, gw_o)


@pytest.mark.parametrize("transpose", [False, True])
def test_butterfly_grad_multiblock_accumulation(transpose):
    """Batch spanning several grid blocks plus a padded remainder exercises
    the in-place float32 dw accumulation across the sequential grid."""
    n = 32
    w = bf.random_weights(jax.random.PRNGKey(3), n)
    x = jax.random.normal(jax.random.PRNGKey(4), (10, n))
    c = jax.random.normal(jax.random.PRNGKey(5), (10, n))

    gx_k, gw_k = jax.grad(
        lambda x, w: jnp.vdot(c, butterfly_matmul(
            x, w, transpose=transpose, block_b=4, interpret=True)),
        argnums=(0, 1))(x, w)
    gx_o, gw_o = jax.grad(
        lambda x, w: jnp.vdot(c, ref.butterfly_ref(w, x,
                                                   transpose=transpose)),
        argnums=(0, 1))(x, w)
    _assert_close(gx_k, gx_o)
    _assert_close(gw_k, gw_o)


def test_butterfly_grad_nd_batch():
    n = 64
    w = bf.random_weights(jax.random.PRNGKey(6), n)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 3, 5, n))
    c = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 5, n))
    gx, gw = jax.grad(
        lambda x, w: jnp.vdot(c, ops.butterfly_apply(
            x, w, context="pallas_interpret")), argnums=(0, 1))(x, w)
    gx_o, gw_o = jax.grad(
        lambda x, w: jnp.vdot(c, ref.butterfly_ref(w, x)),
        argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    _assert_close(gx, gx_o)
    _assert_close(gw, gw_o)


def test_butterfly_grad_bf16_finite():
    """bf16 activations: backward runs, weight grads come back in the weight
    dtype, everything finite (tolerances are meaningless at bf16)."""
    n = 64
    w = bf.random_weights(jax.random.PRNGKey(9), n)
    x = jax.random.normal(jax.random.PRNGKey(10), (5, n)).astype(jnp.bfloat16)
    gx, gw = jax.grad(
        lambda x, w: jnp.sum(ops.butterfly_apply(
            x, w, context="pallas_interpret").astype(jnp.float32) ** 2),
        argnums=(0, 1))(x, w)
    assert gx.dtype == jnp.bfloat16
    assert gw.dtype == w.dtype
    assert bool(jnp.isfinite(gx.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(gw).all())


# ---------------------------------------------------------------------------
# Sandwich VJP vs oracle autodiff
# ---------------------------------------------------------------------------

# Sandwiches whose butterflies fold their in-lane stages onto the MXU
# (n >= 128) run over three row tiles of 8, so the fold blocks' gradients
# accumulate across grid steps. The MXU sums 128 products per output where
# the oracle's stages sum a depth-7 tree, so the two round differently, and
# these gradients reach 10^3: their tolerance is float32's, rel 1e-5 of each
# tensor's scale (the float32 oracle itself is up to 5x the absolute 1e-5 of
# the first two cases from a float64 evaluation).
_TILED = {"rows": 24, "block_b": 8, "scaled": True}


@pytest.mark.parametrize("n1,n2,k1,k2,case", [
    (64, 64, 8, 8, {}),
    (32, 128, 16, 12, {}),
    (1024, 2048, 10, 11, _TILED),          # the MLP up projection's widths
    (2048, 1024, 11, 10, _TILED),          # the MLP down projection's
    (128, 256, 7, 8, _TILED),              # every stage of b_in folded
    (256, 128, 8, 7, _TILED),              # every stage of b_out folded
    (256, 1024, 8, 10, dict(_TILED, chunk_elems=1)),  # cross-chunk stages
], ids=["64-64-8-8", "32-128-16-12", "mlp-up", "mlp-down", "in-all-folded",
        "out-all-folded", "folded-cross-chunk"])
def test_sandwich_grad_matches_oracle(monkeypatch, n1, n2, k1, k2, case):
    """Output and the four gradients (dx, d b_in, d core, d b_out) of the
    interpret-mode kernels against the jnp oracle."""
    if "chunk_elems" in case:
        monkeypatch.setattr(bkern, "CHUNK_ELEMS", case["chunk_elems"])
    rows = case.get("rows", 9)
    spec = bl.make_spec(jax.random.PRNGKey(11), n1, n2, k_in=k1, k_out=k2,
                        use_bias=False)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(12), spec)
    x = jax.random.normal(jax.random.PRNGKey(13), (rows, n1))
    c = jax.random.normal(jax.random.PRNGKey(14), (rows, n2))
    sel_in = one_hot_select(spec.idx_in, n1)
    sel_out = one_hot_select(spec.idx_out, n2).T
    si, so = math.sqrt(n1 / k1), math.sqrt(n2 / k2)
    kernel = exctx.ExecutionContext(backend="pallas_interpret",
                                    block_b=case.get("block_b"))

    def apply(backend):
        return lambda x, b_in, core, b_out: ops.sandwich_apply(
            x, b_in, sel_in, core, sel_out, b_out,
            scale_in=si, scale_out=so, context=backend)

    def close(got, want):
        want = np.asarray(want, np.float32)
        scale = float(np.abs(want).max()) if case.get("scaled") else 1.0
        _assert_close(got, want, atol=1e-5 * scale)

    def loss(backend):
        return lambda *a: jnp.vdot(c, apply(backend)(*a))

    args = (x, params["b_in"], params["core"], params["b_out"])
    close(apply(kernel)(*args), apply("jnp")(*args))
    got = jax.grad(loss(kernel), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3))(*args)
    for g_k, g_o in zip(got, want):
        close(g_k, g_o)


def test_sandwich_sel_matrices_zero_cotangent():
    """The fixed one-hot selection matrices are structural: their cotangents
    are identically zero (they must never receive training signal)."""
    n1 = n2 = 32
    spec = bl.make_spec(jax.random.PRNGKey(15), n1, n2, k_in=4, k_out=4,
                        use_bias=False)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(16), spec)
    x = jax.random.normal(jax.random.PRNGKey(17), (3, n1))
    sel_in = one_hot_select(spec.idx_in, n1)
    sel_out = one_hot_select(spec.idx_out, n2).T

    g_sel = jax.grad(lambda s: jnp.sum(ops.sandwich_apply(
        x, params["b_in"], s, params["core"], sel_out, params["b_out"],
        context="pallas_interpret") ** 2))(sel_in)
    np.testing.assert_array_equal(np.asarray(g_sel), 0.0)


# ---------------------------------------------------------------------------
# Layer/encdec threading: fused path gradients == jnp path gradients
# ---------------------------------------------------------------------------

def test_butterfly_linear_backend_grads_agree():
    """butterfly_linear_apply(context="pallas_interpret") must train exactly
    like the jnp path — including bias and non-power-of-two dims (padding)."""
    spec = bl.make_spec(jax.random.PRNGKey(18), 48, 100, k_in=6, k_out=7,
                        use_bias=True)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(19), spec)
    x = jax.random.normal(jax.random.PRNGKey(20), (5, 48))
    c = jax.random.normal(jax.random.PRNGKey(21), (5, 100))

    def loss(backend):
        return lambda p: jnp.vdot(c, bl.butterfly_linear_apply(
            spec, p, x, context=backend))

    g_k = jax.grad(loss("pallas_interpret"))(params)
    g_o = jax.grad(loss("jnp"))(params)
    assert set(g_k) == set(g_o)
    for name in g_o:
        _assert_close(g_k[name], g_o[name])


def test_encdec_train_step_fused_backend():
    """One encoder-decoder Adam step through the fused kernel path moves the
    loss the same way as the oracle path."""
    from repro.core import encdec
    key = jax.random.PRNGKey(22)
    spec = encdec.make_spec(key, n=16, d=12, k=2)
    params = encdec.init_params(jax.random.PRNGKey(23), spec)
    X = jax.random.normal(jax.random.PRNGKey(24), (16, 12))
    g_k = jax.grad(lambda p: encdec.loss_fn(
        spec, p, X, X, context="pallas_interpret"))(params)
    g_o = jax.grad(lambda p: encdec.loss_fn(
        spec, p, X, X, context="jnp"))(params)
    for name in g_o:
        _assert_close(g_k[name], g_o[name], atol=2e-5)


# ---------------------------------------------------------------------------
# Segmented stage checkpointing: complexity gate + parity across segments
# ---------------------------------------------------------------------------

def _run_bwd_kernel(x, w, g, segment=None, transpose=False):
    """Run the butterfly backward kernel (interpret mode) on one tile of
    ``x``'s rows."""
    jax.block_until_ready(bkern._butterfly_bwd_call(
        x, w, g, transpose, x.shape[0], segment, True))


def test_backward_stage_applies_linear_bound():
    """CI gate for the segmented-checkpoint complexity claim: per-tile stage
    applications in the butterfly backward at n = 4096 must stay within
    3·p'·⌈√p'⌉ (the asserted bound) — and in fact within 3·p',
    since each segment is recomputed exactly once. ``p'`` counts the
    stages left on the VPU: the seven in-lane ones run as the folded MXU
    block, which is no stage application. Every stage the kernel runs
    reports itself to the host counter, so the count is what the loops
    executed on the tile."""
    n = 4096
    p = bf.num_stages(n)
    vpu = p - tuning.folded_stages(n)
    assert vpu == p - 7
    x = jnp.ones((8, n))
    g = jnp.ones((8, n))
    w = jnp.ones((p, 2, n))
    with count_stage_applies() as applied:
        _run_bwd_kernel(x, w, g)
    assert applied() <= 3 * vpu * tuning.default_segment(vpu)  # acceptance
    assert applied() <= 3 * vpu                      # actual linear bound
    # strictly better than the old O(p'²) full-prefix recompute
    assert applied() < vpu * (vpu - 1) // 2 + vpu


def test_backward_stage_applies_bounded_for_all_segments():
    """Every segment size runs exactly the linear count over the p' = p − 7
    VPU stages: the forward checkpoint sweep applies the stages before the
    last segment, each segment but its last stage is recomputed exactly
    once, and the dual cotangent sweep applies all p'. Bᵀ folds its
    in-lane stages last, so its checkpoint sweep runs through the last
    segment too, to the fold block's input."""
    n = 1024
    p = bf.num_stages(n)
    vpu = p - tuning.folded_stages(n)
    assert vpu == p - 7
    x = jnp.ones((4, n))
    g = jnp.ones((4, n))
    w = jnp.ones((p, 2, n))
    for seg in sorted({1, 2, vpu}):
        n_seg = -(-vpu // seg)
        for transpose, sweep in ((False, (n_seg - 1) * seg), (True, vpu)):
            expected = sweep + (vpu - n_seg) + vpu
            with count_stage_applies() as applied:
                _run_bwd_kernel(x, w, g, segment=seg, transpose=transpose)
            assert applied() == expected <= 3 * vpu, (seg, transpose,
                                                      applied())


def test_stage_counter_sees_every_forward_stage():
    """The counter counts runs, not traces: a two-tile forward applies the
    p' = p − 7 VPU stages on each tile (the in-lane seven are the folded
    MXU block)."""
    n = 256
    p = bf.num_stages(n)
    vpu = p - tuning.folded_stages(n)
    assert vpu == 1
    w = jnp.ones((p, 2, n))
    with count_stage_applies() as applied:
        jax.block_until_ready(bkern._butterfly_fwd_call(
            jnp.ones((16, n)), w, False, 8, True))
    assert applied() == 2 * vpu


@pytest.mark.parametrize("transpose", [False, True])
def test_segmented_checkpoint_grad_matches_oracle(transpose):
    """Gradient parity across the whole segment knob range, including the
    VMEM-scratch checkpoint path inside the Pallas kernel (interpret)."""
    n = 64
    p = bf.num_stages(n)
    w = bf.random_weights(jax.random.PRNGKey(30), n)
    x = jax.random.normal(jax.random.PRNGKey(31), (9, n))
    c = jax.random.normal(jax.random.PRNGKey(32), (9, n))
    gx_o, gw_o = jax.grad(
        lambda x, w: jnp.vdot(c, ref.butterfly_ref(w, x,
                                                   transpose=transpose)),
        argnums=(0, 1))(x, w)
    for seg in sorted({1, 2, tuning.default_segment(p), p}):
        gx_k, gw_k = jax.grad(
            lambda x, w: jnp.vdot(c, butterfly_matmul(
                x, w, transpose=transpose, block_b=4, segment=seg,
                interpret=True)), argnums=(0, 1))(x, w)
        _assert_close(gx_k, gx_o)
        _assert_close(gw_k, gw_o)


@settings(max_examples=8, deadline=None)
@given(logn=st.integers(2, 5), seed=st.integers(0, 2 ** 30),
       transpose=st.booleans())
def test_property_segmented_backward_equals_oracle(logn, seed, transpose):
    """Hypothesis sweep: segmented-checkpoint backward equals the jnp-oracle
    gradient for every segment size in {1, 2, ⌈√p⌉, p}."""
    n = 1 << logn
    p = bf.num_stages(n)
    kw, kx, kc = jax.random.split(jax.random.PRNGKey(seed), 3)
    w = bf.random_weights(kw, n)
    x = jax.random.normal(kx, (5, n))
    c = jax.random.normal(kc, (5, n))
    gx_o, gw_o = jax.grad(
        lambda x, w: jnp.vdot(c, ref.butterfly_ref(w, x,
                                                   transpose=transpose)),
        argnums=(0, 1))(x, w)
    for seg in sorted({1, 2, tuning.default_segment(p), p}):
        gx_k, gw_k = jax.grad(
            lambda x, w: jnp.vdot(c, butterfly_matmul(
                x, w, transpose=transpose, block_b=4, segment=seg,
                interpret=True)), argnums=(0, 1))(x, w)
        _assert_close(gx_k, gx_o)
        _assert_close(gw_k, gw_o)


# ---------------------------------------------------------------------------
# bf16 forward/backward parity (relaxed tolerances)
# ---------------------------------------------------------------------------

def _assert_close_bf16(got, want, frac=0.05):
    """bf16 parity: absolute tolerance scaled to the oracle's magnitude."""
    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)
    atol = frac * max(float(np.abs(want).max()), 1e-3)
    np.testing.assert_allclose(got, want, rtol=frac, atol=atol)


@pytest.mark.parametrize("transpose", [False, True])
def test_butterfly_bf16_fwd_bwd_parity(transpose):
    n = 128
    w = bf.random_weights(jax.random.PRNGKey(33), n)
    x = jax.random.normal(jax.random.PRNGKey(34), (7, n)).astype(jnp.bfloat16)
    c = jax.random.normal(jax.random.PRNGKey(35), (7, n)).astype(jnp.bfloat16)
    out = butterfly_matmul(x, w, transpose=transpose, interpret=True)
    want = ref.butterfly_ref(w.astype(jnp.float32),
                             x.astype(jnp.float32), transpose=transpose)
    _assert_close_bf16(out, want)

    def loss(backend_fn):
        return lambda x, w: jnp.vdot(
            c.astype(jnp.float32),
            backend_fn(x, w).astype(jnp.float32))

    gx_k, gw_k = jax.grad(
        loss(lambda x, w: butterfly_matmul(x, w, transpose=transpose,
                                           interpret=True)),
        argnums=(0, 1))(x, w)
    gx_o, gw_o = jax.grad(
        loss(lambda x, w: ref.butterfly_ref(
            w.astype(jnp.float32), x.astype(jnp.float32),
            transpose=transpose)),
        argnums=(0, 1))(x, w)
    assert gx_k.dtype == jnp.bfloat16 and gw_k.dtype == w.dtype
    _assert_close_bf16(gx_k, gx_o)
    _assert_close_bf16(gw_k, gw_o)


def test_sandwich_bf16_fwd_bwd_parity():
    n1, n2, k1, k2 = 64, 128, 8, 8
    spec = bl.make_spec(jax.random.PRNGKey(36), n1, n2, k_in=k1, k_out=k2,
                        use_bias=False)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(37), spec)
    x = jax.random.normal(jax.random.PRNGKey(38), (6, n1)).astype(jnp.bfloat16)
    c = jax.random.normal(jax.random.PRNGKey(39), (6, n2))
    sel_in = one_hot_select(spec.idx_in, n1)
    sel_out = one_hot_select(spec.idx_out, n2).T
    si, so = math.sqrt(n1 / k1), math.sqrt(n2 / k2)

    def fused(x, b_in, core, b_out):
        return sandwich_matmul(x, b_in, sel_in, core, sel_out, b_out,
                               scale_in=si, scale_out=so, interpret=True)

    def oracle(x, b_in, core, b_out):
        return ref.sandwich_ref(x.astype(jnp.float32), b_in, core, b_out,
                                sel_in, sel_out, si, so)

    out = fused(x, params["b_in"], params["core"], params["b_out"])
    want = oracle(x, params["b_in"], params["core"], params["b_out"])
    assert out.dtype == jnp.bfloat16
    _assert_close_bf16(out, want, frac=0.08)

    def loss(f):
        return lambda *a: jnp.vdot(c, f(*a).astype(jnp.float32))

    got = jax.grad(loss(fused), argnums=(0, 1, 2, 3))(
        x, params["b_in"], params["core"], params["b_out"])
    wantg = jax.grad(loss(oracle), argnums=(0, 1, 2, 3))(
        x, params["b_in"], params["core"], params["b_out"])
    for g_k, g_o in zip(got, wantg):
        _assert_close_bf16(g_k, g_o, frac=0.08)


# ---------------------------------------------------------------------------
# Flash attention VJP vs oracle autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                           (False, 0)])
def test_flash_grad_matches_oracle(causal, window):
    from repro.kernels.flash import flash_attention
    B, H, S, D = 2, 3, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(40), 4)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    c = jax.random.normal(ks[3], (B, H, S, D))

    def loss_kernel(q, k, v):
        return jnp.vdot(c, flash_attention(q, k, v, causal=causal,
                                           window=window, block_q=16,
                                           block_kv=16, interpret=True))

    def loss_oracle(q, k, v):
        return jnp.vdot(c, ref.flash_attention_ref(q, k, v, causal=causal,
                                                   window=window))

    got = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_oracle, argnums=(0, 1, 2))(q, k, v)
    for g_k, g_o in zip(got, want):
        _assert_close(g_k, g_o)


def test_flash_grad_mixed_block_shapes():
    """Backward parity when block_q != block_kv (independent sweep bounds
    in the dq and dkv kernels)."""
    from repro.kernels.flash import flash_attention
    B, H, S, D = 1, 2, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(41), 4)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    c = jax.random.normal(ks[3], (B, H, S, D))
    want = jax.grad(lambda q, k, v: jnp.vdot(c, ref.flash_attention_ref(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for bq, bkv in [(32, 64), (64, 32)]:
        got = jax.grad(lambda q, k, v: jnp.vdot(c, flash_attention(
            q, k, v, causal=True, block_q=bq, block_kv=bkv,
            interpret=True)), argnums=(0, 1, 2))(q, k, v)
        for g_k, g_o in zip(got, want):
            _assert_close(g_k, g_o)


def test_flash_autotuned_blocks_divide_seq():
    """The default (tuned) block sizes must divide S and keep the fwd/bwd
    kernels runnable end to end."""
    from repro.kernels.flash import flash_attention
    B, H, S, D = 1, 1, 64, 8
    bq, bkv = tuning.flash_blocks(S, D, "float32", "bwd")
    assert S % bq == 0 and S % bkv == 0
    ks = jax.random.split(jax.random.PRNGKey(42), 3)
    q = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, interpret=True) ** 2))(q)
    assert bool(jnp.isfinite(g).all())


# ---------------------------------------------------------------------------
# Property test: VJP vs finite differences
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(logn=st.integers(1, 4), seed=st.integers(0, 2**30))
def test_property_butterfly_vjp_finite_differences(logn, seed):
    """Directional derivative from the fused VJP matches central finite
    differences in (x, w) jointly on small n (float32 tolerances)."""
    n = 1 << logn
    kw, kx, kc, kdw, kdx = jax.random.split(jax.random.PRNGKey(seed), 5)
    w = bf.random_weights(kw, n)
    x = jax.random.normal(kx, (3, n))
    c = jax.random.normal(kc, (3, n))
    dw = bf.random_weights(kdw, n)
    dx = jax.random.normal(kdx, (3, n))

    def f(x, w):
        return jnp.vdot(c, ops.butterfly_apply(x, w,
                                               context="pallas_interpret"))

    gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
    directional = float(jnp.vdot(gx, dx) + jnp.vdot(gw, dw))
    eps = 1e-3
    fplus = float(f(x + eps * dx, w + eps * dw))
    fminus = float(f(x - eps * dx, w - eps * dw))
    fd = (fplus - fminus) / (2 * eps)
    scale = max(1.0, abs(fd), abs(directional))
    assert abs(directional - fd) <= 5e-3 * scale


@pytest.fixture
def fold_off(monkeypatch):
    """Every stage on the VPU, as in butterflies too wide to fold. Traces
    and tuner choices made with the fold are dropped before and after."""
    def clear():
        jax.clear_caches()
        tuning._tune_cached.cache_clear()

    clear()
    monkeypatch.setattr(tuning, "folded_stages", lambda n: 0)
    yield
    monkeypatch.undo()
    clear()


@pytest.mark.parametrize("transpose", [False, True])
def test_cross_chunk_stages_match_oracle(monkeypatch, fold_off, transpose):
    """Stages whose partner lanes lie in another chunk (stride >= CHUNK)
    pair two chunks up instead of rotating lanes: shrink the chunks to one
    128-lane vreg so a 1024-wide butterfly runs 3 of its 10 stages that
    way, forward and backward. The fold is turned off, so all ten stages
    run on the VPU, as they do in butterflies too wide to fold (the LM
    head's 65,536); the folded path with cross-chunk stages is a case of
    test_sandwich_grad_matches_oracle."""
    monkeypatch.setattr(bkern, "CHUNK_ELEMS", 1)
    n = 1024
    w = bf.random_weights(jax.random.PRNGKey(40), n)
    x = jax.random.normal(jax.random.PRNGKey(41), (9, n))

    def loss(fn):
        return lambda x, w: jnp.sum(jnp.sin(fn(x, w)))

    kernel = loss(lambda x, w: bkern.butterfly_matmul(
        x, w, transpose=transpose, interpret=True))
    oracle = loss(lambda x, w: ref.butterfly_ref(w, x, transpose=transpose))
    np.testing.assert_allclose(kernel(x, w), oracle(x, w), rtol=1e-5)
    for gk, go in zip(jax.grad(kernel, (0, 1))(x, w),
                      jax.grad(oracle, (0, 1))(x, w)):
        _assert_close(gk, go, atol=2e-5)


def test_sandwich_cross_chunk_grad_matches_oracle(monkeypatch, fold_off):
    """The sandwich kernels with both butterflies spanning several chunks
    (chunks shrunk to 128 lanes): chunked selection/scatter products and
    cross-chunk stages, forward and backward, with every stage on the VPU
    (fold off, as in test_cross_chunk_stages_match_oracle)."""
    monkeypatch.setattr(bkern, "CHUNK_ELEMS", 1)
    test_sandwich_grad_matches_oracle(monkeypatch, 256, 1024, 8, 10, {})
