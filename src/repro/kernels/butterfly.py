"""Fused multi-stage butterfly Pallas kernels (TPU target), forward *and*
backward.

TPU adaptation of the paper's butterfly product (DESIGN.md §3): instead of
``log n`` separate sparse matmuls (log n HBM round trips, arithmetic
intensity ~1), a single ``pallas_call`` keeps a ``(block_b, n)`` activation
tile resident in VMEM and applies *all* stages before writing back.

Stage ``s`` is ``y = a_s ⊙ x + b_s ⊙ swap_s(x)`` with ``swap_s(x)[i] =
x[i ^ 2**s]`` — VPU FMA and lane-rotate traffic only, no gather/scatter.
The tile lives in a float32 VMEM buffer that every stage updates in place,
``CHUNK`` lanes at a time: partners closer than a chunk are swapped by two
lane rotations and a select, farther partners pair two chunks up. Stages
and chunks are loops, not unrolled, so the traced kernel is the same size
at n = 1024 and n = 65536.

Training support: ``butterfly_matmul`` carries a :func:`jax.custom_vjp` whose
backward pass is itself a fused Pallas kernel. The butterfly backward is a
(dual) butterfly product interleaved with per-stage weight-gradient
reductions::

    da_s = Σ_batch g_{s+1} ⊙ x_s        db_s = Σ_batch g_{s+1} ⊙ swap_s(x_s)
    g_s  = a_s ⊙ g_{s+1} + swap_s(b_s ⊙ g_{s+1})

**Segmented stage checkpointing.** The reverse sweep needs the stage inputs
``x_s`` in *reverse* order. Recomputing each from the saved input tile costs
O(p²) stage applications per tile (p = log2 n — 13× more VPU work than the
forward at n = 8192). Instead, one forward sweep stashes the activation at
every ``segment``-th stage boundary in a VMEM scratch buffer
(``pl.pallas_call`` ``scratch_shapes``), and the reverse sweep recomputes
only *within* a segment (one pass per segment, into a second scratch):

    stage applications per tile  ≤  p (checkpoint sweep)
                                  + p (within-segment recompute)
                                  + p (dual cotangent sweep)   = O(p)

against ``⌈p/segment⌉ + segment + 2`` float32 ``(block_b, n)`` VMEM tiles —
``segment`` is the VMEM/compute knob, defaulting to ⌈√p⌉ (the live-tile
minimum) via :mod:`repro.kernels.tuning`, which also sizes ``block_b`` so
the whole working set fits the VMEM budget. Weight gradients are accumulated
in float32 across the batch grid: the TPU grid is sequential, so the
``(p, 2, n)`` output block is revisited by every grid step and updated in
place.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.butterfly import num_stages
from repro.kernels import tuning

# Run-time instrumentation: while count_stage_applies() is open, every
# kernel traced reports each stage it applies to the host, so the count is
# what the stage loops executed. It gates the O(p) backward in tests/CI;
# only interpret mode can run the host callback.
_STAGE_COUNTERS: list = []

# The kernels keep a tile in a float32 VMEM buffer and touch it one chunk
# of columns at a time: the widest power-of-two lane count (at least one
# 128-lane vreg) whose (bb, width) chunk holds at most CHUNK_ELEMS values.
# No traced value outgrows a chunk, so Mosaic's compile time stays flat in
# n and in the tile height.
CHUNK_ELEMS = 1 << 16
_LANES = 128
_SUBLANES = 8


@contextlib.contextmanager
def count_stage_applies():
    """Yields a zero-arg callable returning the number of butterfly stage
    applications run, in interpret mode, by kernels traced inside the
    context."""
    count = [0]

    def applied() -> int:
        jax.effects_barrier()
        return count[0]

    _STAGE_COUNTERS.append(count)
    try:
        yield applied
    finally:
        _STAGE_COUNTERS[:] = [c for c in _STAGE_COUNTERS if c is not count]


def _count_stage() -> None:
    """Bump the open stage counters once per run of the enclosing loop
    body (nothing is emitted when no counter is open)."""
    if not _STAGE_COUNTERS:
        return
    counters = tuple(_STAGE_COUNTERS)

    def bump():
        for c in counters:
            c[0] += 1

    jax.debug.callback(bump)


def _chunk(shape) -> int:
    """Chunk width in lanes for a ``(rows, n)`` tile."""
    rows, n = shape[-2:]
    width = min(n, _LANES)
    while width * 2 <= n and width * 2 * rows <= CHUNK_ELEMS:
        width *= 2
    return width


def _cols(c, width: int):
    """Lane slice of chunk ``c`` (``c`` may be traced)."""
    return pl.ds(pl.multiple_of(c * width, width), width)


def _for_chunks(shape, body) -> None:
    """``body(cols)`` for every chunk of a ``(rows, n)`` tile, as one loop."""
    width = _chunk(shape)

    def step(c, carry):
        body(_cols(c, width))
        return carry

    jax.lax.fori_loop(0, shape[-1] // width, step, 0)


def _copy(dst, src) -> None:
    """``dst[...] = src[...]`` chunk by chunk, cast to ``dst``'s dtype."""
    def body(cols):
        dst[:, cols] = src[:, cols].astype(dst.dtype)
    _for_chunks(dst.shape, body)


def _swap_halves(x: jnp.ndarray, stride) -> jnp.ndarray:
    """y[i] = x[i ^ stride] along the last axis, for ``stride`` below the
    axis length: two lane rotations and a select on bit ``stride`` of the
    lane index (Mosaic cannot split the lane axis with a reshape, and
    rotates 32-bit lanes only). ``stride`` may be traced."""
    axis = x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where((lane & stride) != 0,
                     pltpu.roll(x, stride, axis),                  # x[i - s]
                     pltpu.roll(x, x.shape[-1] - stride, axis))    # x[i + s]


# Inside the kernels the (p, 2, n) stage weights travel as float32 rows:
# row 2s is a_s and row 2s + 1 is b_s, padded to whole 8-row sublane tiles
# (weight_rows). A loop-indexed stage reads its aligned 8-row tile and
# rotates its two rows to the top: Mosaic loads and stores dynamic sublane
# offsets only at multiples of the tile height.

def _row_tile(s):
    """(aligned start, offset in the tile) of weight row ``2s``."""
    base = pl.multiple_of((2 * s) // _SUBLANES * _SUBLANES, _SUBLANES)
    return base, 2 * s - base


def _stage_weights(w_ref, s, cols):
    """Stage ``s``'s ``(a, b)`` coefficients on ``cols``, float32
    ``(1, width)``."""
    base, r = _row_tile(s)
    tile = pltpu.roll(w_ref[pl.ds(base, _SUBLANES), cols],
                      (_SUBLANES - r) % _SUBLANES, 0)
    return tile[0:1], tile[1:2]


def _add_stage_grads(dw_ref, s, cols, da, db) -> None:
    """``dw`` rows ``2s``, ``2s + 1`` on ``cols`` += ``da``, ``db``."""
    base, r = _row_tile(s)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, da.shape[-1]), 0)
    dw_ref[pl.ds(base, _SUBLANES), cols] += jnp.where(
        row == r, da, jnp.where(row == r + 1, db, 0.0))


def _rowsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(x, axis=0, keepdims=True)


def _stage_of(j, stages: int, transpose: bool):
    """Stage applied at position ``j`` (Bᵀ applies the strides reversed)."""
    return stages - 1 - j if transpose else j


def _stage(buf, w_ref, s, transpose: bool, *, t_ref=None, dw_ref=None):
    """One butterfly stage, in place on the float32 ``(bb, n)`` buffer:
    ``a ⊙ x + b ⊙ swap(x)``, or with ``transpose`` ``a ⊙ x + swap(b ⊙ x)``,
    stride ``2**s`` (``s`` may be traced).

    With ``t_ref``/``dw_ref`` (the backward's dual sweep: ``buf`` holds the
    cotangent ``g``, ``t_ref`` the stage's input), first adds the weight
    gradients ``Σ_rows g ⊙ t`` and ``Σ_rows g ⊙ swap(t)`` (or, for the
    transposed stage, ``Σ_rows swap(g) ⊙ t``) into ``dw_ref``.

    Partners inside one chunk are swapped by lane rotation; partners in
    another chunk pair the two chunks up, so no value spans more than two
    chunks.
    """
    _count_stage()
    n = buf.shape[-1]
    width = _chunk(buf.shape)
    stride = jnp.left_shift(jnp.int32(1), s)
    # the weight-gradient rule reads g before the (dual) stage rewrites it
    dual = not transpose if t_ref is not None else transpose

    def local(cols):
        x = buf[:, cols]
        a, b = _stage_weights(w_ref, s, cols)
        if t_ref is not None:
            t = t_ref[:, cols]
            db = (_rowsum(_swap_halves(x, stride) * t) if transpose
                  else _rowsum(x * _swap_halves(t, stride)))
            _add_stage_grads(dw_ref, s, cols, _rowsum(x * t), db)
        buf[:, cols] = (a * x + _swap_halves(b * x, stride) if dual
                        else a * x + b * _swap_halves(x, stride))

    def across(k, carry):
        # chunk pair (c0, c1 = c0 | m): k with a zero bit inserted at m
        m = stride // width
        c0 = (k // m) * 2 * m + k % m
        cols0, cols1 = _cols(c0, width), _cols(c0 + m, width)
        x0, x1 = buf[:, cols0], buf[:, cols1]
        a0, b0 = _stage_weights(w_ref, s, cols0)
        a1, b1 = _stage_weights(w_ref, s, cols1)
        if t_ref is not None:
            t0, t1 = t_ref[:, cols0], t_ref[:, cols1]
            db0, db1 = ((_rowsum(x1 * t0), _rowsum(x0 * t1)) if transpose
                        else (_rowsum(x0 * t1), _rowsum(x1 * t0)))
            _add_stage_grads(dw_ref, s, cols0, _rowsum(x0 * t0), db0)
            _add_stage_grads(dw_ref, s, cols1, _rowsum(x1 * t1), db1)
        if dual:      # y[i] = a[i] x[i] + b[i^s] x[i^s]
            buf[:, cols0] = a0 * x0 + b1 * x1
            buf[:, cols1] = a1 * x1 + b0 * x0
        else:         # y[i] = a[i] x[i] + b[i] x[i^s]
            buf[:, cols0] = a0 * x0 + b0 * x1
            buf[:, cols1] = a1 * x1 + b1 * x0
        return carry

    def pairs():
        jax.lax.fori_loop(0, n // width // 2, across, 0)

    if n == width:
        _for_chunks(buf.shape, local)
    else:
        jax.lax.cond(stride < width, lambda: _for_chunks(buf.shape, local),
                     pairs)


def _apply_stages(buf, w_ref, j0, j1, stages: int,
                  transpose: bool) -> None:
    """Application positions ``[j0, j1)`` in place on ``buf``, as one
    loop."""
    def step(j, carry):
        _stage(buf, w_ref, _stage_of(j, stages, transpose), transpose)
        return carry

    jax.lax.fori_loop(j0, j1, step, 0)


def _butterfly_kernel(x_ref, w_ref, o_ref, buf, *, stages: int,
                      transpose: bool):
    # stage math runs in float32: the TPU rotates only 32-bit lanes, and
    # v5e's VPU has no bfloat16 arithmetic anyway
    _copy(buf, x_ref)
    _apply_stages(buf, w_ref, 0, stages, stages, transpose)
    _copy(o_ref, buf)


def _butterfly_bwd_block(buf, gbuf, w_ref, stages: int, transpose: bool,
                         segment: int, ckpt_ref, acts_ref, dw_ref) -> None:
    """VJP of the fused butterfly on one tile, in place: ``buf`` holds the
    input activation (clobbered), ``gbuf`` the output cotangent, which
    becomes ``dx``; the tile's weight gradient is added into ``dw_ref``.

    ``w_ref`` and ``dw_ref`` hold ``(2p, n)`` rows (:func:`_stage_weights`).
    Stage inputs come from segmented checkpointing: a forward sweep stores
    the activation entering position ``j`` for every segment boundary
    ``j ∈ {0, segment, 2·segment, …}`` in ``ckpt_ref``, then each segment
    is recomputed exactly once, into ``acts_ref``, during the reverse sweep
    — O(p) stage applications total instead of the O(p²) full-prefix
    recompute.

    The cotangent rule per stage is the *dual* stage applied to ``g``: the
    transpose of ``a ⊙ x + b ⊙ swap(x)`` is ``a ⊙ g + swap(b ⊙ g)`` and vice
    versa (swap is an involution).
    """
    seg = tuning.resolve_segment(stages, segment or None)
    n_seg = -(-stages // seg)

    def stage(j, carry):
        _stage(buf, w_ref, _stage_of(j, stages, transpose), transpose)
        return carry

    # --- forward sweep: checkpoint the input of every segment ---
    def checkpoint(ci, carry):
        _copy(ckpt_ref.at[ci], buf)
        return jax.lax.fori_loop(ci * seg, (ci + 1) * seg, stage, carry)

    jax.lax.fori_loop(0, n_seg - 1, checkpoint, 0)
    _copy(ckpt_ref.at[n_seg - 1], buf)

    # --- reverse sweep: one within-segment recompute per segment ---
    def segment_vjp(k, carry):
        ci = n_seg - 1 - k
        j0 = ci * seg
        j1 = jnp.minimum(j0 + seg, stages)
        _copy(buf, ckpt_ref.at[ci])

        def recompute(j, carry):
            _copy(acts_ref.at[j - j0], buf)
            return stage(j, carry)

        jax.lax.fori_loop(j0, j1 - 1, recompute, 0)
        _copy(acts_ref.at[j1 - 1 - j0], buf)

        def dual(i, carry):
            j = j1 - 1 - i
            _stage(gbuf, w_ref, _stage_of(j, stages, transpose), transpose,
                   t_ref=acts_ref.at[j - j0], dw_ref=dw_ref)
            return carry

        return jax.lax.fori_loop(0, j1 - j0, dual, carry)

    jax.lax.fori_loop(0, n_seg, segment_vjp, 0)


def _zero_on_first_step(*refs) -> None:
    """Zero accumulator outputs on the first grid step: the TPU grid is
    sequential and every step revisits (and adds into) the same block."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in refs:
            r[...] = jnp.zeros_like(r)


def _butterfly_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, buf, gbuf,
                          ckpt_ref, acts_ref, *, stages: int,
                          transpose: bool, segment: int):
    _zero_on_first_step(dw_ref)
    _copy(buf, x_ref)
    _copy(gbuf, g_ref)
    _butterfly_bwd_block(buf, gbuf, w_ref, stages, transpose, segment,
                         ckpt_ref, acts_ref, dw_ref)
    _copy(dx_ref, gbuf)


def tile_scratch(bb: int, n: int):
    """One float32 ``(bb, n)`` working tile in VMEM."""
    return pltpu.VMEM((bb, n), jnp.float32)


def checkpoint_scratch(stages: int, seg: int, bb: int, n: int):
    """VMEM scratch of the segmented backward: segment-boundary checkpoints
    and one segment's recomputed stage inputs, float32."""
    return [pltpu.VMEM((len(range(0, stages, seg)), bb, n), jnp.float32),
            pltpu.VMEM((seg, bb, n), jnp.float32)]


def weight_rows(w: jnp.ndarray) -> jnp.ndarray:
    """``(p, 2, n)`` stage weights as the kernels' float32 rows (see
    :func:`_stage_weights`); :func:`weight_grads` undoes it."""
    p, two, n = w.shape
    rows = w.astype(jnp.float32).reshape(two * p, n)
    return jnp.pad(rows, ((0, _padded_rows(p) - two * p), (0, 0)))


def _padded_rows(p: int) -> int:
    return -(-2 * p // _SUBLANES) * _SUBLANES


def weight_grads(dw_rows: jnp.ndarray, p: int) -> jnp.ndarray:
    """Kernel weight-gradient rows back to ``(p, 2, n)``."""
    return dw_rows[:2 * p].reshape(p, 2, -1)


def _flatten_batch(x: jnp.ndarray, block_b: int):
    """Flatten leading axes into a batch dim padded to a block multiple."""
    lead = x.shape[:-1]
    n = x.shape[-1]
    b = 1
    for d in lead:
        b *= d
    x2 = x.reshape(b, n)
    bb = min(block_b, b)
    padded_b = -(-b // bb) * bb
    if padded_b != b:
        x2 = jnp.pad(x2, ((0, padded_b - b), (0, 0)))
    return x2, lead, b, bb, padded_b


def _butterfly_fwd_call(x: jnp.ndarray, w: jnp.ndarray, transpose: bool,
                        block_b, interpret: bool) -> jnp.ndarray:
    p, two, n = w.shape
    assert two == 2 and (1 << p) == n, f"bad weight shape {w.shape}"
    stages = num_stages(n)
    block_b = tuning.resolve_block_b("butterfly", n, x.dtype, "fwd", block_b)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    grid = (padded_b // bb,)
    out = pl.pallas_call(
        functools.partial(_butterfly_kernel, stages=stages,
                          transpose=transpose),
        name="butterfly_matmul_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
            pl.BlockSpec((_padded_rows(p), n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_b, n), x.dtype),
        scratch_shapes=[tile_scratch(bb, n)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(w))
    return out[:b].reshape(*lead, n)


def _butterfly_bwd_call(x: jnp.ndarray, w: jnp.ndarray, g: jnp.ndarray,
                        transpose: bool, block_b, segment, interpret: bool):
    p, _, n = w.shape
    stages = num_stages(n)
    block_b = tuning.resolve_block_b("butterfly", n, x.dtype, "bwd", block_b)
    seg = tuning.resolve_segment(stages, segment, kernel="butterfly", n=n,
                                 dtype=x.dtype)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    g2, _, _, _, _ = _flatten_batch(g.astype(x.dtype), block_b)
    grid = (padded_b // bb,)
    dx, dw = pl.pallas_call(
        functools.partial(_butterfly_bwd_kernel, stages=stages,
                          transpose=transpose, segment=seg),
        name="butterfly_matmul_bwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
            pl.BlockSpec((_padded_rows(p), n), lambda i: (0, 0)),
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, n), lambda i: (i, 0)),
            pl.BlockSpec((_padded_rows(p), n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded_b, n), x.dtype),
            jax.ShapeDtypeStruct((_padded_rows(p), n), jnp.float32),
        ],
        scratch_shapes=([tile_scratch(bb, n), tile_scratch(bb, n)]
                        + checkpoint_scratch(stages, seg, bb, n)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(x2, weight_rows(w), g2)
    return dx[:b].reshape(*lead, n), weight_grads(dw, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _butterfly_diff(x, w, transpose, block_b, segment, interpret):
    return _butterfly_fwd_call(x, w, transpose, block_b, interpret)


def _butterfly_diff_fwd(x, w, transpose, block_b, segment, interpret):
    # Residuals are just (x, w): the backward kernel recomputes stage
    # activations from the input tile via segmented checkpointing, so
    # nothing else is stashed in HBM.
    return _butterfly_fwd_call(x, w, transpose, block_b, interpret), (x, w)


def _butterfly_diff_bwd(transpose, block_b, segment, interpret, res, g):
    x, w = res
    dx, dw = _butterfly_bwd_call(x, w, g, transpose, block_b, segment,
                                 interpret)
    return dx, dw.astype(w.dtype)


_butterfly_diff.defvjp(_butterfly_diff_fwd, _butterfly_diff_bwd)


@functools.partial(jax.jit,
                   static_argnames=("transpose", "block_b", "segment",
                                    "interpret"))
def butterfly_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                     transpose: bool = False,
                     block_b=None, segment=None,
                     interpret: bool = False) -> jnp.ndarray:
    """Fused butterfly product ``B x`` (or ``Bᵀ x``) over the last axis.

    ``x``: (..., n) with n a power of two; ``w``: (p, 2, n).
    Leading axes are flattened into a batch grid. Differentiable in both
    ``x`` and ``w`` via a fused Pallas backward kernel (custom_vjp) with
    segmented stage checkpointing. ``block_b`` (batch-tile rows, per
    direction) and ``segment`` (backward checkpoint interval) default to the
    :mod:`repro.kernels.tuning` autotuner; pass ints only to override it.
    """
    return _butterfly_diff(x, w, transpose, block_b, segment, interpret)
