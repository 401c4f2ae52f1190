"""Fused butterfly-sandwich Pallas kernels (TPU target), forward and backward.

Computes the paper's full dense-layer replacement ``J2ᵀ · W' · J1 · x`` in a
single VMEM residency per activation tile:

    butterfly(b_in) → truncate (one-hot MXU matmul) → small dense core (MXU)
    → scatter (one-hot MXU matmul) → transposed butterfly(b_out)

Truncation/scatter are lowered as multiplications with fixed one-hot matrices
(``sel_in``: (n1, k1), ``sel_out``: (k2, n2)) — TPU has no fast dynamic
gather across lanes, but one-hot matmuls ride the MXU (DESIGN.md §3).

Five HBM round trips (one per op in the unfused jnp path) collapse into one.

Training support: ``sandwich_matmul`` carries a :func:`jax.custom_vjp` whose
backward pass is one fused Pallas kernel chaining, per activation tile:

    recompute forward intermediates from the saved input tile
    → butterfly-transpose VJP (per-stage ``da/db`` reductions)
    → one-hot scatter/selection transposes
    → small-dense-core gradient ``dW' = dh₂ᵀ h₁`` (MXU)
    → input-butterfly VJP → dx

Both butterfly VJPs use the segmented stage checkpointing of
:func:`repro.kernels.butterfly._butterfly_bwd_block` — each butterfly gets
its own VMEM scratch buffer for the ⌈p/segment⌉ boundary activations, so
per-tile stage applications stay O(p) instead of the old O(p²) full-prefix
recompute. ``block_b`` and the checkpoint segments default to the
:mod:`repro.kernels.tuning` autotuner.

Weight gradients (both butterflies + core) accumulate in float32 across the
sequential batch grid into revisited output blocks. The fixed one-hot
selection matrices get zero cotangents (they are structural, never trained).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.butterfly import num_stages
from repro.kernels import tuning
from repro.kernels.butterfly import (_apply_stages, _butterfly_bwd_block,
                                     _chunk, _cols, _copy, _flatten_batch,
                                     _for_chunks, _padded_rows,
                                     _zero_on_first_step, checkpoint_scratch,
                                     tile_scratch, weight_grads, weight_rows)

__all__ = ["sandwich_matmul", "one_hot_select"]


def _dot(a, b, contract=(1, 0)):
    """float32 MXU product contracting ``a``'s axis ``contract[0]`` with
    ``b``'s axis ``contract[1]`` (transposes ride the matmul, not a copy)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32)


def _sum_chunks(shape, part, init):
    """``init + Σ part(cols)`` over the chunks of a ``(rows, n)`` tile."""
    width = _chunk(shape)
    return jax.lax.fori_loop(0, shape[-1] // width,
                             lambda c, acc: acc + part(_cols(c, width)),
                             init)


def _sandwich_forward_block(buf_in, buf_out, w_in_ref, sel_in_ref, core_ref,
                            sel_out_ref, *, stages_in: int, scale_in: float,
                            scale_out: float):
    """Shared forward math up to the scatter output ``z`` (pre
    out-butterfly), in float32 VMEM buffers like the butterfly stages
    (:mod:`repro.kernels.butterfly`): ``buf_in`` holds ``x`` and is run
    through the input butterfly in place; ``buf_out`` receives ``z``.

    Returns ``h1``, which the core gradient needs in backward.
    """
    _apply_stages(buf_in, w_in_ref, 0, stages_in, stages_in, False)
    h1 = _sum_chunks(buf_in.shape, lambda cols: _dot(buf_in[:, cols],
                                           sel_in_ref[cols, :]),
                     jnp.zeros((buf_in.shape[0], sel_in_ref.shape[1]),
                               jnp.float32))
    h2 = _dot(h1 * scale_in, core_ref[...], (1, 1))        # (bb, k2)

    def scatter(cols):
        buf_out[:, cols] = _dot(h2, sel_out_ref[:, cols]) * scale_out

    _for_chunks(buf_out.shape, scatter)
    return h1 * scale_in                                    # (bb, k1)


def _sandwich_kernel(x_ref, w_in_ref, sel_in_ref, core_ref, sel_out_ref,
                     w_out_ref, o_ref, buf_in, buf_out, *, stages_in: int,
                     stages_out: int, scale_in: float, scale_out: float):
    _copy(buf_in, x_ref)
    _sandwich_forward_block(buf_in, buf_out, w_in_ref, sel_in_ref, core_ref,
                            sel_out_ref, stages_in=stages_in,
                            scale_in=scale_in, scale_out=scale_out)
    _apply_stages(buf_out, w_out_ref, 0, stages_out, stages_out, True)
    _copy(o_ref, buf_out)


def _sandwich_bwd_kernel(x_ref, w_in_ref, sel_in_ref, core_ref, sel_out_ref,
                         w_out_ref, g_ref, dx_ref, dwin_ref, dcore_ref,
                         dwout_ref, buf_in, gbuf_in, buf_out, gbuf_out,
                         ckpt_out_ref, acts_out_ref, ckpt_in_ref,
                         acts_in_ref, *, stages_in: int, stages_out: int,
                         seg_in: int, seg_out: int, scale_in: float,
                         scale_out: float):
    _zero_on_first_step(dwin_ref, dcore_ref, dwout_ref)
    # --- recompute forward intermediates (VMEM-resident, no stash) ---
    _copy(buf_in, x_ref)
    h1 = _sandwich_forward_block(buf_in, buf_out, w_in_ref, sel_in_ref,
                                 core_ref, sel_out_ref, stages_in=stages_in,
                                 scale_in=scale_in, scale_out=scale_out)
    # --- VJP through the output (transposed) butterfly: gbuf_out -> dz ---
    _copy(gbuf_out, g_ref)
    _butterfly_bwd_block(buf_out, gbuf_out, w_out_ref, stages_out, True,
                         seg_out, ckpt_out_ref, acts_out_ref, dwout_ref)
    # --- scatter / core / selection chain (float32 on the MXU) ---
    dh2 = _sum_chunks(gbuf_out.shape, lambda cols: _dot(gbuf_out[:, cols],
                                            sel_out_ref[:, cols], (1, 1)),
                      jnp.zeros((x_ref.shape[0], sel_out_ref.shape[0]),
                                jnp.float32)) * scale_out   # (bb, k2)
    dcore_ref[...] += _dot(dh2, h1, (0, 0))                  # (k2, k1)
    dh1 = _dot(dh2, core_ref[...]) * scale_in                # (bb, k1)

    def scatter_back(cols):
        gbuf_in[:, cols] = _dot(dh1, sel_in_ref[cols, :], (1, 1))

    _for_chunks(gbuf_in.shape, scatter_back)
    # --- VJP through the input butterfly: gbuf_in -> dx ---
    _copy(buf_in, x_ref)
    _butterfly_bwd_block(buf_in, gbuf_in, w_in_ref, stages_in, False,
                         seg_in, ckpt_in_ref, acts_in_ref, dwin_ref)
    _copy(dx_ref, gbuf_in)


@functools.lru_cache(maxsize=None)
def one_hot_select_np(idx: tuple, n: int) -> np.ndarray:
    """Cached numpy (n, k) one-hot with column j selecting idx[j].

    The cache deliberately holds *numpy* arrays: a jax array built inside a
    jit trace is a tracer, and caching one at module level leaks it into
    later traces (UnexpectedTracerError). Callers convert per use — the
    scatter construction is the cached part.
    """
    sel = np.zeros((n, len(idx)), dtype=np.float32)
    sel[np.asarray(idx), np.arange(len(idx))] = 1.0
    return sel


def one_hot_select(idx, n: int, dtype=jnp.float32) -> jnp.ndarray:
    """(n, k) one-hot matrix with column j selecting coordinate idx[j].

    Backed by a module-level cache on ``(idx, n)``: the index sets are
    frozen at layer init, so non-layers callers (benchmarks, encdec, tests)
    stop rebuilding the numpy one-hot on every trace.
    """
    sel = one_hot_select_np(tuple(int(i) for i in idx), int(n))
    return jnp.asarray(sel, dtype=dtype)


def _sandwich_specs(bb, n1, n2, p1, p2, k1, k2):
    return [
        pl.BlockSpec((bb, n1), lambda i: (i, 0)),
        pl.BlockSpec((_padded_rows(p1), n1), lambda i: (0, 0)),
        pl.BlockSpec((n1, k1), lambda i: (0, 0)),
        pl.BlockSpec((k2, k1), lambda i: (0, 0)),
        pl.BlockSpec((k2, n2), lambda i: (0, 0)),
        pl.BlockSpec((_padded_rows(p2), n2), lambda i: (0, 0)),
    ]


def _sandwich_fwd_call(x, b_in, sel_in, core, sel_out, b_out, scale_in,
                       scale_out, block_b, interpret):
    p1, _, n1 = b_in.shape
    p2, _, n2 = b_out.shape
    k1 = sel_in.shape[1]
    k2 = sel_out.shape[0]
    assert core.shape == (k2, k1), (core.shape, k1, k2)
    block_b = tuning.resolve_block_b("sandwich", max(n1, n2), x.dtype,
                                     "fwd", block_b)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    grid = (padded_b // bb,)
    out = pl.pallas_call(
        functools.partial(_sandwich_kernel, stages_in=num_stages(n1),
                          stages_out=num_stages(n2),
                          scale_in=scale_in, scale_out=scale_out),
        grid=grid,
        in_specs=_sandwich_specs(bb, n1, n2, p1, p2, k1, k2),
        out_specs=pl.BlockSpec((bb, n2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_b, n2), x.dtype),
        scratch_shapes=[tile_scratch(bb, n1), tile_scratch(bb, n2)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(b_in), sel_in.astype(x.dtype), core,
      sel_out, weight_rows(b_out))
    return out[:b].reshape(*lead, n2)


def _sandwich_bwd_call(x, b_in, sel_in, core, sel_out, b_out, g, scale_in,
                       scale_out, block_b, segment, interpret):
    p1, _, n1 = b_in.shape
    p2, _, n2 = b_out.shape
    k1 = sel_in.shape[1]
    k2 = sel_out.shape[0]
    stages_in = num_stages(n1)
    stages_out = num_stages(n2)
    block_b = tuning.resolve_block_b("sandwich", max(n1, n2), x.dtype,
                                     "bwd", block_b)
    seg_in = tuning.resolve_segment(stages_in, segment, kernel="sandwich",
                                    n=max(n1, n2), dtype=x.dtype)
    seg_out = tuning.resolve_segment(stages_out, segment, kernel="sandwich",
                                     n=max(n1, n2), dtype=x.dtype)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    g2, _, _, _, _ = _flatten_batch(g.astype(x.dtype), block_b)
    grid = (padded_b // bb,)
    in_specs = _sandwich_specs(bb, n1, n2, p1, p2, k1, k2)
    in_specs.append(pl.BlockSpec((bb, n2), lambda i: (i, 0)))
    dx, dwin, dcore, dwout = pl.pallas_call(
        functools.partial(_sandwich_bwd_kernel, stages_in=stages_in,
                          stages_out=stages_out, seg_in=seg_in,
                          seg_out=seg_out, scale_in=scale_in,
                          scale_out=scale_out),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, n1), lambda i: (i, 0)),
            pl.BlockSpec((_padded_rows(p1), n1), lambda i: (0, 0)),
            pl.BlockSpec((k2, k1), lambda i: (0, 0)),
            pl.BlockSpec((_padded_rows(p2), n2), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_b, n1), x.dtype),
            jax.ShapeDtypeStruct((_padded_rows(p1), n1), jnp.float32),
            jax.ShapeDtypeStruct((k2, k1), jnp.float32),
            jax.ShapeDtypeStruct((_padded_rows(p2), n2), jnp.float32),
        ],
        scratch_shapes=([tile_scratch(bb, n1), tile_scratch(bb, n1),
                         tile_scratch(bb, n2), tile_scratch(bb, n2)]
                        + checkpoint_scratch(stages_out, seg_out, bb, n2)
                        + checkpoint_scratch(stages_in, seg_in, bb, n1)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(b_in), sel_in.astype(x.dtype), core,
      sel_out, weight_rows(b_out), g2)
    return (dx[:b].reshape(*lead, n1), weight_grads(dwin, p1), dcore,
            weight_grads(dwout, p2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _sandwich_diff(x, b_in, sel_in, core, sel_out, b_out, scale_in,
                   scale_out, block_b, segment, interpret):
    return _sandwich_fwd_call(x, b_in, sel_in, core, sel_out, b_out,
                              scale_in, scale_out, block_b, interpret)


def _sandwich_diff_fwd(x, b_in, sel_in, core, sel_out, b_out, scale_in,
                       scale_out, block_b, segment, interpret):
    out = _sandwich_fwd_call(x, b_in, sel_in, core, sel_out, b_out,
                             scale_in, scale_out, block_b, interpret)
    return out, (x, b_in, sel_in, core, sel_out, b_out)


def _sandwich_diff_bwd(scale_in, scale_out, block_b, segment, interpret,
                       res, g):
    x, b_in, sel_in, core, sel_out, b_out = res
    dx, dwin, dcore, dwout = _sandwich_bwd_call(
        x, b_in, sel_in, core, sel_out, b_out, g, scale_in, scale_out,
        block_b, segment, interpret)
    # one-hot selection matrices are structural constants — zero cotangent
    return (dx, dwin.astype(b_in.dtype), jnp.zeros_like(sel_in),
            dcore.astype(core.dtype), jnp.zeros_like(sel_out),
            dwout.astype(b_out.dtype))


_sandwich_diff.defvjp(_sandwich_diff_fwd, _sandwich_diff_bwd)


@functools.partial(jax.jit, static_argnames=("scale_in", "scale_out",
                                             "block_b", "segment",
                                             "interpret"))
def sandwich_matmul(x: jnp.ndarray, b_in: jnp.ndarray, sel_in: jnp.ndarray,
                    core: jnp.ndarray, sel_out: jnp.ndarray,
                    b_out: jnp.ndarray, *, scale_in: float = 1.0,
                    scale_out: float = 1.0, block_b=None, segment=None,
                    interpret: bool = False) -> jnp.ndarray:
    """Fused sandwich over the last axis: (..., n1) -> (..., n2).

    ``b_in``: (p1, 2, n1); ``sel_in``: (n1, k1); ``core``: (k2, k1);
    ``sel_out``: (k2, n2); ``b_out``: (p2, 2, n2). n1/n2 powers of two.
    Differentiable in ``x``, ``b_in``, ``core`` and ``b_out`` via a fused
    Pallas backward kernel (custom_vjp) with segmented stage checkpointing
    for both butterflies; the one-hot selection matrices get zero
    cotangents. ``block_b``/``segment`` default to the
    :mod:`repro.kernels.tuning` autotuner.
    """
    return _sandwich_diff(x, b_in, sel_in, core, sel_out, b_out,
                          scale_in, scale_out, block_b, segment, interpret)
