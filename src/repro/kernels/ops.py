"""Public jit'd entry points for the Pallas kernels.

Execution policy — backend, Pallas tile sizes, mesh — is carried by one
object, :class:`repro.kernels.context.ExecutionContext`, passed as the
``context=`` argument (an :class:`ExecutionContext`, a bare backend string,
or ``None``) or installed ambiently with ``with use_execution(ctx):``. The
resolution order is context > ambient > config default > ``REPRO_*`` env >
autotune/platform; see :mod:`repro.kernels.context`.

* On TPU the default resolves to the compiled Pallas kernels (Mosaic) — for
  inference *and* training: every fused kernel carries a
  :func:`jax.custom_vjp` with a fused Pallas backward pass, so ``jax.grad``
  through these entry points stays on the fast path.
* On CPU (this container) the default resolves to the *pure-jnp oracles*,
  while tests request ``context="pallas_interpret"`` to execute the kernel
  bodies — forward and backward — in Python without hardware.
* A context with ``mesh_shape``/``mesh`` routes the call through
  :mod:`repro.runtime.butterfly_sharding`: activations batch-sharded via
  ``shard_map``, stage weights replicated, weight gradients psum'd through
  the fused custom_vjp backward.
* ``block_b``/``segment`` left unset defer to the
  :mod:`repro.kernels.tuning` VMEM/roofline autotuner.

The pre-context loose kwargs (``backend=``, ``block_b=``, ``segment=``,
``mesh=``, ``mesh_axes=``) are gone — their one-release deprecation shim
was removed; ``context=`` is the only execution-policy argument.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import context as exctx
from repro.kernels import ref as _ref
from repro.kernels.butterfly import butterfly_matmul as _butterfly_pallas
from repro.kernels.context import (Backend, ExecutionContext,
                                   clear_backend_cache, resolve_backend,
                                   use_execution)
from repro.kernels.sandwich import sandwich_matmul as _sandwich_pallas
from repro.kernels.sandwich import one_hot_select


def _sharded_route(ctx: ExecutionContext):
    """Resolve a finalized context to (sharding module, axes) when it asks
    for (and the mesh supports) multi-device execution, else None. Imported
    lazily: runtime.butterfly_sharding wraps these entry points, so a
    top-level import would be circular."""
    if ctx.mesh is None:
        return None
    from repro.runtime import butterfly_sharding as bsh
    axes = bsh.data_axes(ctx.mesh, ctx.mesh_axes)
    return (bsh, axes) if axes else None


def _local_butterfly(x: jnp.ndarray, w: jnp.ndarray, *, transpose: bool,
                     ctx: ExecutionContext) -> jnp.ndarray:
    """Single-device dispatch on a *finalized* context: no resolution, no
    mesh routing. The shard_map region closures in
    :mod:`repro.runtime.butterfly_sharding` call this directly so an
    ambient mesh context can never re-route a call that is already inside
    its own shard."""
    if ctx.backend == "jnp":
        return _ref.butterfly_ref(w.astype(x.dtype), x, transpose=transpose)
    with use_execution(ctx):  # tuning overrides (vmem_budget) see ctx
        return _butterfly_pallas(
            x, w, transpose=transpose,
            block_b=ctx.block_b, segment=ctx.segment,
            interpret=ctx.backend == "pallas_interpret")


def butterfly_apply(x: jnp.ndarray, w: jnp.ndarray, *,
                    transpose: bool = False,
                    context: exctx.ContextLike = None) -> jnp.ndarray:
    """Fused butterfly product over the last axis of ``x``.

    Differentiable under every backend; the Pallas backends use the fused
    custom_vjp backward kernel with segmented stage checkpointing. All
    execution knobs ride ``context`` (module docstring); a context with a
    mesh batch-shards the call over its data axes.
    """
    ctx = exctx.resolve_execution(context)
    route = _sharded_route(ctx)
    if route is not None:
        bsh, axes = route
        return bsh.sharded_butterfly_apply(x, w, context=ctx, axes=axes,
                                           transpose=transpose)
    return _local_butterfly(x, w, transpose=transpose, ctx=ctx)


def sandwich_apply(x: jnp.ndarray, b_in: jnp.ndarray, sel_in: jnp.ndarray,
                   core: jnp.ndarray, sel_out: jnp.ndarray,
                   b_out: jnp.ndarray, *, scale_in: float = 1.0,
                   scale_out: float = 1.0,
                   context: exctx.ContextLike = None) -> jnp.ndarray:
    """Fused butterfly sandwich (dense-layer replacement) over the last axis.

    Differentiable under every backend; the Pallas backends use the fused
    custom_vjp backward kernel with segmented stage checkpointing. All
    execution knobs ride ``context`` (module docstring).
    """
    ctx = exctx.resolve_execution(context)
    route = _sharded_route(ctx)
    if route is not None:
        bsh, axes = route
        return bsh.sharded_sandwich_apply(
            x, b_in, sel_in, core, sel_out, b_out, context=ctx, axes=axes,
            scale_in=scale_in, scale_out=scale_out)
    return _local_sandwich(x, b_in, sel_in, core, sel_out, b_out,
                           scale_in=scale_in, scale_out=scale_out, ctx=ctx)


def _local_sandwich(x, b_in, sel_in, core, sel_out, b_out, *,
                    scale_in: float, scale_out: float,
                    ctx: ExecutionContext) -> jnp.ndarray:
    """Single-device sandwich dispatch on a finalized context (see
    :func:`_local_butterfly`)."""
    if ctx.backend == "jnp":
        return _ref.sandwich_ref(x, b_in, core, b_out, sel_in, sel_out,
                                 scale_in, scale_out)
    with use_execution(ctx):
        return _sandwich_pallas(
            x, b_in, sel_in, core, sel_out, b_out,
            scale_in=scale_in, scale_out=scale_out,
            block_b=ctx.block_b, segment=ctx.segment,
            interpret=ctx.backend == "pallas_interpret")


__all__ = ["butterfly_apply", "sandwich_apply", "one_hot_select", "Backend",
           "ExecutionContext", "use_execution", "resolve_backend",
           "clear_backend_cache"]
