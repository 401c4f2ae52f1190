"""Fused butterfly-sandwich Pallas kernels (TPU target), forward and backward.

Computes the paper's full dense-layer replacement ``J2ᵀ · W' · J1 · x`` in a
single VMEM residency per activation tile:

    butterfly(b_in) → truncate (one-hot MXU matmul) → small dense core (MXU)
    → scatter (one-hot MXU matmul) → transposed butterfly(b_out)

Truncation/scatter are lowered as multiplications with fixed one-hot matrices
(``sel_in``: (n1, k1), ``sel_out``: (k2, n2)) — TPU has no fast dynamic
gather across lanes, but one-hot matmuls ride the MXU (DESIGN.md §3).

Each butterfly runs as :mod:`repro.kernels.butterfly` runs it: where its
width ``n ≥ 128`` and the fold block fits VMEM (every MLP side at SmolLM's
widths, 1024 and 2048, and the LM head's 1024-wide input side; not the
head's 65,536-wide output), the seven in-lane stages (strides 1…64) are one
float32 128×128 block per lane group, applied on the MXU at
``precision=HIGHEST`` — first in ``b_in``, right after the input is copied
in; last in ``b_out``, after its last VPU stage. The other stages stay on
the VPU. The blocks are built from the stage weights outside the kernel
(:func:`repro.kernels.butterfly.fold_block`).

Five HBM round trips (one per op in the unfused jnp path) collapse into one.

Training support: ``sandwich_matmul`` carries a :func:`jax.custom_vjp` whose
backward pass is one fused Pallas kernel chaining, per activation tile:

    recompute forward intermediates from the saved input tile
    → output-butterfly VJP: checkpoint sweep over its VPU stages to the
      fold's input Z, then dZ = G·Mᵀ and dM_out += Zᵀ·G (MXU), then the
      dual sweep over the VPU stages (per-stage ``da/db`` reductions)
    → one-hot scatter/selection transposes
    → small-dense-core gradient ``dW' = dh₂ᵀ h₁`` (MXU)
    → input-butterfly VJP: dual sweep over its VPU stages to dY, then
      dx = dY·Mᵀ and dM_in += xᵀ·dY (MXU)

Both butterflies' VPU stages use the segmented stage checkpointing of
:func:`repro.kernels.butterfly._butterfly_bwd_block` — each butterfly gets
its own VMEM scratch buffer for the ⌈p'/segment⌉ boundary activations, so
per-tile stage applications stay O(p'). ``block_b`` and the checkpoint
segments default to the :mod:`repro.kernels.tuning` autotuner.

Weight gradients (both butterflies' VPU stages and fold blocks, and the
core) accumulate in float32 across the sequential batch grid into revisited
output blocks. The folded stages' weight gradients are the VJP of the block
builder at the kernel's ``dM``, taken after the call (weight-only, exact).
The fixed one-hot selection matrices get zero cotangents (they are
structural, never trained).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning
from repro.kernels.butterfly import (_LANES, _butterfly_forward,
                                     _butterfly_vjp, _chunk, _cols, _copy,
                                     _dot, _flatten_batch, _for_chunks,
                                     _padded_rows, _zero_on_first_step,
                                     checkpoint_scratch, fold_block,
                                     fold_weight_grads, resident,
                                     tile_scratch, weight_grads, weight_rows)

__all__ = ["sandwich_matmul", "one_hot_select"]


def _sum_chunks(shape, part, init):
    """``init + Σ part(cols)`` over the chunks of a ``(rows, n)`` tile."""
    width = _chunk(shape)
    return jax.lax.fori_loop(0, shape[-1] // width,
                             lambda c, acc: acc + part(_cols(c, width)),
                             init)


def _sandwich_forward_block(buf_in, buf_out, w_in_ref, m_in_ref, sel_in_ref,
                            core_ref, sel_out_ref, *, lo_in: int,
                            stages_in: int, scale_in: float,
                            scale_out: float):
    """Shared forward math up to the scatter output ``z`` (pre
    out-butterfly), in float32 VMEM buffers like the butterfly stages
    (:mod:`repro.kernels.butterfly`): ``buf_in`` holds ``x`` and is run
    through the input butterfly in place; ``buf_out`` receives ``z``.

    Returns ``h1``, which the core gradient needs in backward.
    """
    _butterfly_forward(buf_in, w_in_ref, m_in_ref, lo_in, stages_in, False)
    h1 = _sum_chunks(buf_in.shape, lambda cols: _dot(buf_in[:, cols],
                                           sel_in_ref[cols, :]),
                     jnp.zeros((buf_in.shape[0], sel_in_ref.shape[1]),
                               jnp.float32))
    h2 = _dot(h1 * scale_in, core_ref[...], (1, 1))        # (bb, k2)

    def scatter(cols):
        buf_out[:, cols] = _dot(h2, sel_out_ref[:, cols]) * scale_out

    _for_chunks(buf_out.shape, scatter)
    return h1 * scale_in                                    # (bb, k1)


def _take(refs, count: int, folds=()):
    """The next ``count`` refs of the iterator ``refs``, then one per entry
    of ``folds``: the next ref where it is set, None where that butterfly
    does not fold (its block was never passed)."""
    return ([next(refs) for _ in range(count)]
            + [next(refs) if f else None for f in folds])


def _sandwich_kernel(*refs, lo_in: int, lo_out: int, stages_in: int,
                     stages_out: int, scale_in: float, scale_out: float):
    refs = iter(refs)
    (x_ref, w_in_ref, sel_in_ref, core_ref, sel_out_ref, w_out_ref,
     m_in_ref, m_out_ref) = _take(refs, 6, (lo_in, lo_out))
    o_ref, buf_in, buf_out = refs
    _copy(buf_in, x_ref)
    _sandwich_forward_block(buf_in, buf_out, w_in_ref, m_in_ref, sel_in_ref,
                            core_ref, sel_out_ref, lo_in=lo_in,
                            stages_in=stages_in, scale_in=scale_in,
                            scale_out=scale_out)
    _butterfly_forward(buf_out, w_out_ref, m_out_ref, lo_out, stages_out,
                       True)
    _copy(o_ref, buf_out)


def _sandwich_bwd_kernel(*refs, lo_in: int, lo_out: int, stages_in: int,
                         stages_out: int, seg_in: int, seg_out: int,
                         scale_in: float, scale_out: float):
    refs = iter(refs)
    (x_ref, w_in_ref, sel_in_ref, core_ref, sel_out_ref, w_out_ref, g_ref,
     m_in_ref, m_out_ref) = _take(refs, 7, (lo_in, lo_out))
    (dx_ref, dwin_ref, dcore_ref, dwout_ref,
     dmin_ref, dmout_ref) = _take(refs, 4, (lo_in, lo_out))
    (buf_in, gbuf_in, buf_out, gbuf_out, ckpt_out_ref, acts_out_ref,
     ckpt_in_ref, acts_in_ref) = refs
    _zero_on_first_step(dwin_ref, dcore_ref, dwout_ref, dmin_ref, dmout_ref)
    # --- recompute forward intermediates (VMEM-resident, no stash) ---
    _copy(buf_in, x_ref)
    h1 = _sandwich_forward_block(buf_in, buf_out, w_in_ref, m_in_ref,
                                 sel_in_ref, core_ref, sel_out_ref,
                                 lo_in=lo_in, stages_in=stages_in,
                                 scale_in=scale_in, scale_out=scale_out)
    # --- VJP through the output (transposed) butterfly: gbuf_out -> dz ---
    _copy(gbuf_out, g_ref)
    _butterfly_vjp(buf_out, gbuf_out, None, w_out_ref, m_out_ref, dwout_ref,
                   dmout_ref, lo_out, stages_out, True, seg_out,
                   ckpt_out_ref, acts_out_ref)
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
    _butterfly_vjp(buf_in, gbuf_in, x_ref, w_in_ref, m_in_ref, dwin_ref,
                   dmin_ref, lo_in, stages_in, False, seg_in, ckpt_in_ref,
                   acts_in_ref)
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
        resident((_padded_rows(p1), n1)),
        resident((n1, k1)),
        resident((k2, k1)),
        resident((k2, n2)),
        resident((_padded_rows(p2), n2)),
    ]


def _fold_specs(b_in, b_out, lo_in: int, lo_out: int):
    """BlockSpecs and arrays of the fold blocks of the butterflies that
    fold: ``b_in``'s (applied first) and ``b_out``'s (transposed, last)."""
    specs, blocks = [], []
    for w, lo, transpose in ((b_in, lo_in, False), (b_out, lo_out, True)):
        if lo:
            specs.append(resident((_LANES, w.shape[-1])))
            blocks.append(fold_block(w, transpose))
    return specs, blocks


def _sandwich_fwd_call(x, b_in, sel_in, core, sel_out, b_out, scale_in,
                       scale_out, block_b, interpret):
    p1, _, n1 = b_in.shape
    p2, _, n2 = b_out.shape
    k1 = sel_in.shape[1]
    k2 = sel_out.shape[0]
    assert core.shape == (k2, k1), (core.shape, k1, k2)
    lo_in, lo_out = tuning.folded_stages(n1), tuning.folded_stages(n2)
    block_b = tuning.resolve_block_b("sandwich", n1, x.dtype, "fwd", block_b,
                                     n_out=n2)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    fold_specs, blocks = _fold_specs(b_in, b_out, lo_in, lo_out)
    out = pl.pallas_call(
        functools.partial(_sandwich_kernel, lo_in=lo_in, lo_out=lo_out,
                          stages_in=p1, stages_out=p2,
                          scale_in=scale_in, scale_out=scale_out),
        name="sandwich_matmul_fwd",
        grid=(padded_b // bb,),
        in_specs=_sandwich_specs(bb, n1, n2, p1, p2, k1, k2) + fold_specs,
        out_specs=pl.BlockSpec((bb, n2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_b, n2), x.dtype),
        scratch_shapes=[tile_scratch(bb, n1), tile_scratch(bb, n2)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(b_in), sel_in.astype(x.dtype), core,
      sel_out, weight_rows(b_out), *blocks)
    return out[:b].reshape(*lead, n2)


def _sandwich_bwd_call(x, b_in, sel_in, core, sel_out, b_out, g, scale_in,
                       scale_out, block_b, segment, interpret):
    """``(dx, db_in, dcore, db_out)``, the butterflies' weight gradients
    float32, folded stages included."""
    p1, _, n1 = b_in.shape
    p2, _, n2 = b_out.shape
    k1 = sel_in.shape[1]
    k2 = sel_out.shape[0]
    lo_in, lo_out = tuning.folded_stages(n1), tuning.folded_stages(n2)
    block_b = tuning.resolve_block_b("sandwich", n1, x.dtype, "bwd", block_b,
                                     n_out=n2)
    seg_in, seg_out = (
        tuning.resolve_segment(p - lo, segment, kernel="sandwich", n=n1,
                               dtype=x.dtype, n_out=n2)
        for p, lo in ((p1, lo_in), (p2, lo_out)))
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    g2, _, _, _, _ = _flatten_batch(g.astype(x.dtype), block_b)
    fold_specs, blocks = _fold_specs(b_in, b_out, lo_in, lo_out)
    in_specs = _sandwich_specs(bb, n1, n2, p1, p2, k1, k2)
    in_specs.append(pl.BlockSpec((bb, n2), lambda i: (i, 0)))
    dm_shapes = [jax.ShapeDtypeStruct(m.shape, jnp.float32) for m in blocks]
    dx, dwin, dcore, dwout, *dms = pl.pallas_call(
        functools.partial(_sandwich_bwd_kernel, lo_in=lo_in, lo_out=lo_out,
                          stages_in=p1, stages_out=p2, seg_in=seg_in,
                          seg_out=seg_out, scale_in=scale_in,
                          scale_out=scale_out),
        name="sandwich_matmul_bwd",
        grid=(padded_b // bb,),
        in_specs=in_specs + fold_specs,
        out_specs=[
            pl.BlockSpec((bb, n1), lambda i: (i, 0)),
            resident((_padded_rows(p1), n1)),
            resident((k2, k1)),
            resident((_padded_rows(p2), n2)),
        ] + fold_specs,
        out_shape=[
            jax.ShapeDtypeStruct((padded_b, n1), x.dtype),
            jax.ShapeDtypeStruct((_padded_rows(p1), n1), jnp.float32),
            jax.ShapeDtypeStruct((k2, k1), jnp.float32),
            jax.ShapeDtypeStruct((_padded_rows(p2), n2), jnp.float32),
        ] + dm_shapes,
        scratch_shapes=([tile_scratch(bb, n1), tile_scratch(bb, n1),
                         tile_scratch(bb, n2), tile_scratch(bb, n2)]
                        + checkpoint_scratch(p2 - lo_out, seg_out, bb, n2)
                        + checkpoint_scratch(p1 - lo_in, seg_in, bb, n1)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(b_in), sel_in.astype(x.dtype), core,
      sel_out, weight_rows(b_out), g2, *blocks)
    dwin, dwout = weight_grads(dwin, p1), weight_grads(dwout, p2)
    dms = iter(dms)
    if lo_in:
        dwin = dwin + fold_weight_grads(b_in, False, next(dms))
    if lo_out:
        dwout = dwout + fold_weight_grads(b_out, True, next(dms))
    return dx[:b].reshape(*lead, n1), dwin, dcore, dwout


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
