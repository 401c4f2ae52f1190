"""Multi-device sharded execution of the fused butterfly kernels.

This is the distributed-runtime integration for :mod:`repro.kernels`: the
fused ``butterfly_matmul`` / ``sandwich_matmul`` / ``butterfly_linear_apply``
entry points wrapped in ``shard_map`` over the data-parallel mesh axes
(``("data",)`` on a single pod, ``("pod", "data")`` across pods):

  * **activations** are batch-sharded — the flattened leading axes of ``x``
    split across the data axes, each shard running the single-device fused
    kernel on its rows;
  * **stage weights stay replicated** — a butterfly layer is ``O(n log n)``
    parameters, tiny next to its activations, so every device holds the full
    ``(p, 2, n)`` stack (the ``stages``/``butterfly_pair``/``butterfly_n``
    and ``butterfly_core_*``/``butterfly_bias`` rules in
    :mod:`repro.runtime.sharding` say the same thing declaratively);
  * **weight gradients are psum'd**: the backward region runs the kernels'
    existing fused ``custom_vjp`` per shard (each shard sees only its batch
    rows, so its ``dw`` is a partial sum) and all-reduces the weight
    cotangents over the data axes before returning them replicated.

The psum lives in an explicit outer :func:`jax.custom_vjp` rather than in
``shard_map``'s transpose so the replicated-weight gradient semantics never
depend on ``shard_map``'s replication check (``check_vma``) — the same
reason :mod:`repro.runtime.pipeline` disables the
check around its ppermute schedule.

Execution policy arrives as a finalized
:class:`repro.kernels.context.ExecutionContext` (``context.mesh`` is the
mesh to shard over); each shard runs the kernel under ``context.local()`` —
the same policy with the mesh stripped — which also keys the lru-cached
region closures, keeping jit cache keys stable.

Batch sizes that do not divide the data-axis product are zero-padded up to
the next multiple and sliced back after the region; the pad/slice pair is
linear, so autodiff routes zero cotangents through the padding rows and
gradients are exact (validated against the single-device jnp oracle in
``tests/test_sharding_butterfly.py`` on 8 simulated devices).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels import context as exctx
from repro.kernels import ops as kops
from repro.runtime.compat import shard_map_compat

__all__ = [
    "shard_map_compat",
    "data_axes",
    "shard_count",
    "shard_batch_apply",
    "sharded_butterfly_apply",
    "sharded_sandwich_apply",
    "sharded_butterfly_linear_apply",
]

# Candidate batch axes, outermost first — matches the DEFAULT_RULES "batch"
# entry in repro.runtime.sharding.
BATCH_AXIS_CANDIDATES: Tuple[str, ...] = ("pod", "data")


def data_axes(mesh: Optional[Mesh],
              axes: Optional[Sequence[str]] = None) -> Tuple[str, ...]:
    """Mesh axes to batch-shard over: the requested ``axes`` (default
    ``("pod", "data")``) filtered to axes the mesh actually has with size
    > 1. Empty tuple means "don't shard" (callers fall back to the
    single-device path)."""
    if mesh is None:
        return ()
    cand = BATCH_AXIS_CANDIDATES if axes is None else tuple(axes)
    return tuple(a for a in cand if mesh.shape.get(a, 1) > 1)


def shard_count(mesh: Mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64)) \
        if axes else 1


def _shard_ctx(context: exctx.ContextLike,
               axes: Optional[Sequence[str]]):
    """(finalized ctx, per-shard local ctx, axes to shard over)."""
    ctx = exctx.resolve_execution(context)
    axes = data_axes(ctx.mesh, ctx.mesh_axes if axes is None else axes)
    return ctx, ctx.local(), axes


# ---------------------------------------------------------------------------
# Generic batch-sharded wrapper with explicit psum'd weight gradients
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sharded_core(closure, x2, weights):
    """``closure = (fn, mesh, axes)``; ``fn(x_shard, weights) -> y_shard``
    on 2-D ``(rows, n)`` batches. All static pieces ride the hashable
    closure so jit caching keys on them."""
    fn, mesh, axes = closure
    wspecs = jax.tree_util.tree_map(lambda _: P(), weights)
    return shard_map_compat(
        fn, mesh=mesh, in_specs=(P(axes), wspecs),
        out_specs=P(axes))(x2, weights)


def _sharded_core_fwd(closure, x2, weights):
    # Residuals are (x2, weights): the inner kernels' custom_vjp recomputes
    # everything else from the input tile, so nothing extra crosses HBM.
    return _sharded_core(closure, x2, weights), (x2, weights)


def _sharded_core_bwd(closure, res, g2):
    fn, mesh, axes = closure
    x2, weights = res
    wspecs = jax.tree_util.tree_map(lambda _: P(), weights)

    def region(xl, gl, wl):
        _, vjp = jax.vjp(fn, xl, wl)
        dx, dw = vjp(gl)
        # each shard's dw is the partial sum over its batch rows — the fused
        # backward kernels already reduce over the local batch grid, so one
        # all-reduce over the data axes finishes the global reduction
        dw = jax.tree_util.tree_map(lambda d: jax.lax.psum(d, axes), dw)
        return dx, dw

    return shard_map_compat(
        region, mesh=mesh,
        in_specs=(P(axes), P(axes), wspecs),
        out_specs=(P(axes), wspecs))(x2, g2, weights)


_sharded_core.defvjp(_sharded_core_fwd, _sharded_core_bwd)


def shard_batch_apply(fn, x: jnp.ndarray, weights, mesh: Mesh,
                      axes: Sequence[str]) -> jnp.ndarray:
    """Run ``fn(x2, weights)`` with the flattened batch of ``x`` sharded
    over ``axes`` and ``weights`` replicated.

    ``fn`` maps ``(rows, n_in) -> (rows, n_out)`` and must be a stable
    (cached) callable — its identity is part of the jit cache key. Batches
    that don't divide the shard count are zero-padded and sliced back;
    leading axes of ``x`` are restored on the output.
    """
    nsh = shard_count(mesh, tuple(axes))
    lead = x.shape[:-1]
    b = int(np.prod(lead, dtype=np.int64)) if lead else 1
    x2 = x.reshape(b, x.shape[-1])
    padded_b = -(-b // nsh) * nsh
    if padded_b != b:
        x2 = jnp.pad(x2, ((0, padded_b - b), (0, 0)))
    y2 = _sharded_core((fn, mesh, tuple(axes)), x2, weights)
    return y2[:b].reshape(*lead, y2.shape[-1])


# ---------------------------------------------------------------------------
# Kernel-specific wrappers (cached closures keep jit keys stable; the
# per-shard ExecutionContext is hashable and part of the closure key)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _butterfly_fn(transpose, local_ctx):
    # the region runs the non-routing local dispatch: re-entering the public
    # entry point here would re-resolve the ambient context and try to
    # shard_map again from inside the shard
    def fn(x2, w):
        return kops._local_butterfly(x2, w, transpose=transpose,
                                     ctx=local_ctx)
    return fn


def sharded_butterfly_apply(x: jnp.ndarray, w: jnp.ndarray, *,
                            context: exctx.ContextLike,
                            axes: Optional[Sequence[str]] = None,
                            transpose: bool = False) -> jnp.ndarray:
    """Batch-sharded fused butterfly product (see module docstring)."""
    ctx, local_ctx, axes = _shard_ctx(context, axes)
    if not axes:
        return kops._local_butterfly(x, w, transpose=transpose,
                                     ctx=local_ctx)
    fn = _butterfly_fn(transpose, local_ctx)
    return shard_batch_apply(fn, x, w, ctx.mesh, axes)


@functools.lru_cache(maxsize=None)
def _sandwich_fn(scale_in, scale_out, local_ctx):
    def fn(x2, weights):
        b_in, sel_in, core, sel_out, b_out = weights
        return kops._local_sandwich(x2, b_in, sel_in, core, sel_out, b_out,
                                    scale_in=scale_in, scale_out=scale_out,
                                    ctx=local_ctx)
    return fn


def sharded_sandwich_apply(x: jnp.ndarray, b_in: jnp.ndarray,
                           sel_in: jnp.ndarray, core: jnp.ndarray,
                           sel_out: jnp.ndarray, b_out: jnp.ndarray, *,
                           context: exctx.ContextLike,
                           axes: Optional[Sequence[str]] = None,
                           scale_in: float = 1.0, scale_out: float = 1.0
                           ) -> jnp.ndarray:
    """Batch-sharded fused butterfly sandwich (see module docstring)."""
    ctx, local_ctx, axes = _shard_ctx(context, axes)
    if not axes:
        return kops._local_sandwich(x, b_in, sel_in, core, sel_out, b_out,
                                    scale_in=scale_in, scale_out=scale_out,
                                    ctx=local_ctx)
    fn = _sandwich_fn(scale_in, scale_out, local_ctx)
    return shard_batch_apply(fn, x, (b_in, sel_in, core, sel_out, b_out),
                             ctx.mesh, axes)


@functools.lru_cache(maxsize=None)
def _linear_fn(spec, local_ctx):
    # deferred import: core.layers routes back here when a mesh is set
    from repro.core import layers as blayers

    def fn(x2, params):
        return blayers._local_linear_apply(spec, params, x2, local_ctx)
    return fn


def sharded_butterfly_linear_apply(spec, params: dict, x: jnp.ndarray, *,
                                   context: exctx.ContextLike,
                                   axes: Optional[Sequence[str]] = None
                                   ) -> jnp.ndarray:
    """Batch-sharded whole-sandwich layer: padding, kernel dispatch and bias
    all run inside the shard_map region, so the bias gradient is psum'd with
    the other weights."""
    ctx, local_ctx, axes = _shard_ctx(context, axes)
    if not axes:
        from repro.core import layers as blayers
        return blayers._local_linear_apply(spec, params, x, local_ctx)
    fn = _linear_fn(spec, local_ctx)
    return shard_batch_apply(fn, x, dict(params), ctx.mesh, axes)
