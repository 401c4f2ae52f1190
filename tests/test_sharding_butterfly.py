"""Sharded fused butterfly kernels on 8 simulated devices.

Parity gate for :mod:`repro.runtime.butterfly_sharding`, driven purely
through :class:`repro.kernels.context.ExecutionContext` (``mesh_shape``
builds the mesh; no loose kwargs anywhere): batch-sharded ``shard_map``
execution of ``butterfly_apply`` / ``sandwich_apply`` /
``butterfly_linear_apply`` — forward AND ``jax.grad`` (input + every weight
cotangent, psum'd across shards) — must match the single-device jnp oracle
to atol 1e-5, on ``("data",)`` and ``("pod", "data")`` meshes, for batch
sizes that do and do not divide the data-axis product. ``conftest.py``
provides the 8 simulated host devices.

Cost note: every case compiles an 8-way SPMD program (tens of seconds on
CPU), and the ``pallas_interpret`` cases additionally run the kernel bodies
in Python per shard. The full matrix is therefore slow-marked and enforced
by the CI multi-device step (which runs this file without ``-m``); the
tier-1 ``-m "not slow"`` pass keeps a single-compile smoke plus the pure
axis-resolution tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import butterfly as bf
from repro.core import layers as bl
from repro.kernels import ops as kops
from repro.kernels.context import ExecutionContext, use_execution
from repro.kernels.sandwich import one_hot_select
from repro.launch.mesh import simulated_mesh
from repro.runtime import butterfly_sharding as bsh

# fused-kernel backends exercised INSIDE the shard_map region; the oracle
# side is always the single-device jnp reference. Interpret mode executes
# the exact Pallas kernel bodies (fwd + the fused custom_vjp bwd), which is
# what validates the TPU-target kernels under shard_map without hardware.
BACKENDS = ["jnp", "pallas_interpret"]

# 16 divides the 8-way data axis; 11 pads to 16 and exercises the zero-pad
# rows (forward slice + zero cotangents in backward)
BATCHES = [16, 11]

# (8,) -> ("data",) mesh; (2, 4) -> ("pod", "data") — both 8 devices, both
# built by the context itself (launch.mesh.butterfly_mesh)
MESH_SHAPES = [(8,), (2, 4)]
MESH_IDS = ["data8", "pod2xdata4"]

slow = pytest.mark.slow


def _ctx(backend, mesh_shape) -> ExecutionContext:
    return ExecutionContext(backend=backend, mesh_shape=mesh_shape)


def _assert_close(got, want, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-5, atol=atol)


def _grads(loss, *args):
    return jax.grad(loss, argnums=tuple(range(len(args))))(*args)


def _butterfly_case(mesh_shape, batch, backend, transpose, n=64):
    ctx = _ctx(backend, mesh_shape)
    w = bf.random_weights(jax.random.PRNGKey(0), n)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, n))
    c = jax.random.normal(jax.random.PRNGKey(2), (batch, n))

    def sharded(x, w):
        return jnp.vdot(c, kops.butterfly_apply(
            x, w, transpose=transpose, context=ctx))

    def oracle(x, w):
        return jnp.vdot(c, kops.butterfly_apply(
            x, w, transpose=transpose, context="jnp"))

    y_sh = kops.butterfly_apply(x, w, transpose=transpose, context=ctx)
    y_o = kops.butterfly_apply(x, w, transpose=transpose, context="jnp")
    assert y_sh.shape == (batch, n)
    _assert_close(y_sh, y_o)

    gx_sh, gw_sh = _grads(sharded, x, w)
    gx_o, gw_o = _grads(oracle, x, w)
    _assert_close(gx_sh, gx_o)
    _assert_close(gw_sh, gw_o)


# ---------------------------------------------------------------------------
# tier-1 smoke: one compile on the ("data",) mesh, non-divisible batch
# ---------------------------------------------------------------------------

def test_sharded_butterfly_smoke():
    _butterfly_case((8,), batch=11, backend="jnp", transpose=False, n=32)


# ---------------------------------------------------------------------------
# butterfly_apply — full matrix (CI multi-device step)
# ---------------------------------------------------------------------------

@slow
@pytest.mark.parametrize("mesh_shape", MESH_SHAPES, ids=MESH_IDS)
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("transpose", [False, True])
def test_sharded_butterfly_parity(mesh_shape, batch, backend, transpose):
    _butterfly_case(mesh_shape, batch, backend, transpose)


@slow
def test_sharded_butterfly_nd_batch():
    """Leading axes flatten into the sharded batch and are restored."""
    n = 32
    ctx = _ctx("jnp", (8,))
    w = bf.random_weights(jax.random.PRNGKey(3), n)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, n))  # 15 rows: pads
    y_sh = kops.butterfly_apply(x, w, context=ctx)
    y_o = kops.butterfly_apply(x, w, context="jnp")
    assert y_sh.shape == x.shape
    _assert_close(y_sh, y_o)


@slow
def test_sharded_butterfly_under_jit_ambient_context():
    """An ambient use_execution block shards a jitted loss — no per-call
    kwargs at all."""
    n = 32
    ctx = _ctx("jnp", (8,))
    w = bf.random_weights(jax.random.PRNGKey(5), n)
    x = jax.random.normal(jax.random.PRNGKey(6), (11, n))

    @jax.jit
    def loss(x, w):
        with use_execution(ctx):
            return jnp.sum(kops.butterfly_apply(x, w) ** 2)

    want = jnp.sum(kops.butterfly_apply(x, w, context="jnp") ** 2)
    _assert_close(loss(x, w), want, atol=1e-4)
    gx = jax.jit(jax.grad(loss))(x, w)
    gx_o = jax.grad(lambda x: jnp.sum(kops.butterfly_apply(
        x, w, context="jnp") ** 2))(x)
    _assert_close(gx, gx_o, atol=1e-4)


# ---------------------------------------------------------------------------
# sandwich_apply — ("data",) matrix + one ("pod", "data") case; the
# multi-axis psum machinery is shared with the butterfly tests above
# ---------------------------------------------------------------------------

def _sandwich_case(mesh_shape, batch, backend):
    ctx = _ctx(backend, mesh_shape)
    # 128 folds every stage of b_in onto the MXU, 256 all but one of b_out
    n1, n2, k1, k2 = 128, 256, 8, 6
    spec = bl.make_spec(jax.random.PRNGKey(7), n1, n2, k_in=k1, k_out=k2,
                        use_bias=False)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(8), spec)
    sel_in = one_hot_select(spec.idx_in, n1)
    sel_out = one_hot_select(spec.idx_out, n2).T
    x = jax.random.normal(jax.random.PRNGKey(9), (batch, n1))
    c = jax.random.normal(jax.random.PRNGKey(10), (batch, n2))

    def call(x, b_in, core, b_out, **kw):
        return kops.sandwich_apply(x, b_in, sel_in, core, sel_out, b_out,
                                   scale_in=1.5, scale_out=0.5, **kw)

    def sharded(x, b_in, core, b_out):
        return jnp.vdot(c, call(x, b_in, core, b_out, context=ctx))

    def oracle(x, b_in, core, b_out):
        return jnp.vdot(c, call(x, b_in, core, b_out, context="jnp"))

    args = (x, params["b_in"], params["core"], params["b_out"])
    _assert_close(call(*args, context=ctx), call(*args, context="jnp"))
    for g_sh, g_o in zip(_grads(sharded, *args), _grads(oracle, *args)):
        _assert_close(g_sh, g_o)


@slow
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_sandwich_parity(batch, backend):
    _sandwich_case((8,), batch, backend)


@slow
def test_sharded_sandwich_pod_data_mesh():
    _sandwich_case((2, 4), 11, "jnp")


# ---------------------------------------------------------------------------
# butterfly_linear_apply (whole layer: padding + kernel + bias in-region)
# ---------------------------------------------------------------------------

def _linear_case(mesh_shape, batch, backend):
    ctx = _ctx(backend, mesh_shape)
    n_in, n_out = 48, 80  # non-power-of-two: exercises in-region padding
    spec = bl.make_spec(jax.random.PRNGKey(11), n_in, n_out, use_bias=True)
    params = bl.init_butterfly_linear(jax.random.PRNGKey(12), spec)
    params["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(13),
                                             (n_out,))
    x = jax.random.normal(jax.random.PRNGKey(14), (batch, n_in))
    c = jax.random.normal(jax.random.PRNGKey(15), (batch, n_out))

    def sharded(params, x):
        return jnp.vdot(c, bl.butterfly_linear_apply(
            spec, params, x, context=ctx))

    def oracle(params, x):
        return jnp.vdot(c, bl.butterfly_linear_apply(
            spec, params, x, context="jnp"))

    y_sh = bl.butterfly_linear_apply(spec, params, x, context=ctx)
    y_o = bl.butterfly_linear_apply(spec, params, x, context="jnp")
    assert y_sh.shape == (batch, n_out)
    _assert_close(y_sh, y_o)

    (gp_sh, gx_sh) = _grads(sharded, params, x)
    (gp_o, gx_o) = _grads(oracle, params, x)
    _assert_close(gx_sh, gx_o)
    for k in gp_o:
        _assert_close(gp_sh[k], gp_o[k])


@slow
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_sharded_linear_apply_parity(batch, backend):
    _linear_case((8,), batch, backend)


@slow
def test_sharded_linear_apply_pod_data_mesh():
    _linear_case((2, 4), 11, "jnp")


# ---------------------------------------------------------------------------
# repro.nn module API through the same sharded context
# ---------------------------------------------------------------------------

@slow
def test_sharded_nn_butterfly_linear():
    """ButterflyLinear.apply under a mesh context == its single-device
    self — the module facade rides the exact same sharded path."""
    from repro import nn

    layer = nn.ButterflyLinear.create(jax.random.PRNGKey(30), 48, 80,
                                      use_bias=True)
    params = layer.init(jax.random.PRNGKey(31))
    x = jax.random.normal(jax.random.PRNGKey(32), (11, 48))
    ctx = _ctx("jnp", (8,))
    _assert_close(layer.apply(params, x, context=ctx),
                  layer.apply(params, x, context="jnp"))


# ---------------------------------------------------------------------------
# encdec apply_B: shards the transposed product's leading dim (the d data
# COLUMNS of X, not its n rows) — gate that orientation explicitly
# ---------------------------------------------------------------------------

@slow
def test_sharded_encdec_apply_b_parity():
    from repro.core import encdec

    ctx = _ctx("jnp", (8,))
    spec = encdec.make_spec(jax.random.PRNGKey(18), n=50, d=22, k=4)
    params = encdec.init_params(jax.random.PRNGKey(19), spec)
    X = jax.random.normal(jax.random.PRNGKey(20), (50, 22))  # d=22 pads

    Xt_sh = encdec.apply_B(spec, params["B"], X, context=ctx)
    Xt_o = encdec.apply_B(spec, params["B"], X, context="jnp")
    assert Xt_sh.shape == (spec.ell, 22)
    _assert_close(Xt_sh, Xt_o)

    def loss(p, context="jnp"):
        return encdec.loss_fn(spec, p, X, X, context=context)

    _assert_close(loss(params, context=ctx), loss(params), atol=1e-3)
    g_sh = jax.grad(lambda p: loss(p, context=ctx))(params)
    g_o = jax.grad(loss)(params)
    for k in g_o:
        _assert_close(g_sh[k], g_o[k], atol=1e-4)


# ---------------------------------------------------------------------------
# axis resolution / degenerate meshes (cheap, tier-1)
# ---------------------------------------------------------------------------

def test_data_axes_resolution():
    mesh = simulated_mesh(8)
    assert bsh.data_axes(mesh) == ("data",)
    assert bsh.data_axes(mesh, ("data",)) == ("data",)
    assert bsh.data_axes(mesh, ("model",)) == ()
    assert bsh.data_axes(None) == ()
    pd = simulated_mesh(8, ("pod", "data"), (2, 4))
    assert bsh.data_axes(pd) == ("pod", "data")
    assert bsh.shard_count(pd, ("pod", "data")) == 8


def test_trivial_mesh_falls_back_to_local_path():
    """A context whose mesh has no data axes > 1 must not emit shard_map."""
    n = 32
    ctx = ExecutionContext(backend="jnp", mesh=simulated_mesh(1, ("data",),
                                                              (1,)))
    w = bf.random_weights(jax.random.PRNGKey(16), n)
    x = jax.random.normal(jax.random.PRNGKey(17), (5, n))
    assert bsh.data_axes(ctx.mesh) == ()
    y = kops.butterfly_apply(x, w, context=ctx)
    _assert_close(y, kops.butterfly_apply(x, w, context="jnp"))


@slow
def test_mesh_axes_restriction_in_context():
    """ExecutionContext.mesh_axes limits which axes shard: restricting the
    pod2xdata4 mesh to ("data",) still matches the oracle (4-way shard)."""
    n = 32
    ctx = ExecutionContext(backend="jnp", mesh_shape=(2, 4),
                           mesh_axes=("data",))
    w = bf.random_weights(jax.random.PRNGKey(21), n)
    x = jax.random.normal(jax.random.PRNGKey(22), (10, n))
    y = kops.butterfly_apply(x, w, context=ctx)
    _assert_close(y, kops.butterfly_apply(x, w, context="jnp"))
