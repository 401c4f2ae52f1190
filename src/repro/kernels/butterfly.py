"""Fused multi-stage butterfly Pallas kernels (TPU target), forward *and*
backward.

TPU adaptation of the paper's butterfly product (DESIGN.md §3): instead of
``log n`` separate sparse matmuls (log n HBM round trips, arithmetic
intensity ~1), a single ``pallas_call`` keeps a ``(block_b, n)`` activation
tile resident in VMEM and applies *all* stages before writing back.

Stage ``s`` is ``y = a_s ⊙ x + b_s ⊙ swap_s(x)`` with ``swap_s(x)[i] =
x[i ^ 2**s]``. Two engines share the stages:

* **The MXU takes the in-lane stages.** Stages s = 0…6 (strides 1…64)
  never mix two 128-lane groups, so composed they are one dense 128×128
  matrix per lane group. :func:`fold_block` builds them from the weights
  (plain jnp, outside the kernel) as a float32 ``(128, n)`` array ``M``
  whose column block ``g`` is group ``g``'s matrix, and the kernel applies
  them as ``x[:, g] @ M[:, g]``: float32 operands, float32 result,
  ``precision=HIGHEST`` (float32-faithful products). ``B`` applies them
  first, ``Bᵀ`` (strides reversed) last. Whether an ``n``-wide butterfly
  folds is decided by ``n`` alone (:func:`repro.kernels.tuning.folded_stages`):
  where ``n ≥ 128`` and the block, double-buffered with its gradient, fits
  the VMEM budget — every width up to 4096, not the LM head's 65,536.
* **The VPU takes the rest** (all stages where the butterfly does not
  fold): VPU FMA and lane-rotate traffic only, no gather/scatter. The tile
  lives in a float32 VMEM buffer that every stage updates in place,
  ``CHUNK`` lanes at a time: partners closer than a chunk are swapped by
  two lane rotations and a select, farther partners pair two chunks up.
  Stages and chunks are loops, not unrolled, so the traced kernel is the
  same size at n = 1024 and n = 65536.

Training support: ``butterfly_matmul`` carries a :func:`jax.custom_vjp` whose
backward pass is itself a fused Pallas kernel. The butterfly backward is a
(dual) butterfly product interleaved with per-stage weight-gradient
reductions::

    da_s = Σ_batch g_{s+1} ⊙ x_s        db_s = Σ_batch g_{s+1} ⊙ swap_s(x_s)
    g_s  = a_s ⊙ g_{s+1} + swap_s(b_s ⊙ g_{s+1})

The folded block's VJP is two MXU products per lane group, ``dU = dV·Mᵀ``
and ``dM += Uᵀ·dV`` (``U`` its input, ``dV`` its output's cotangent), with
``dM`` accumulated across the batch grid like the stage weights'
gradients. The folded stages' weight gradients are the VJP of
:func:`fold_block` at the kernel's ``dM``, taken after the call: weight-only
and exact.

**Segmented stage checkpointing.** The reverse sweep needs the VPU stages'
inputs ``x_s`` in *reverse* order. Recomputing each from the saved input
tile costs O(p²) stage applications per tile. Instead, one forward sweep
stashes the activation at every ``segment``-th stage boundary in a VMEM
scratch buffer (``pl.pallas_call`` ``scratch_shapes``), and the reverse
sweep recomputes only *within* a segment (one pass per segment, into a
second scratch). With ``p'`` VPU stages:

    stage applications per tile  ≤  p' (checkpoint sweep)
                                  + p' (within-segment recompute)
                                  + p' (dual cotangent sweep)   = O(p')

against ``⌈p'/segment⌉ + segment + 2`` float32 ``(block_b, n)`` VMEM tiles —
``segment`` is the VMEM/compute knob, defaulting to ⌈√p'⌉ (the live-tile
minimum) via :mod:`repro.kernels.tuning`, which also sizes ``block_b`` so
the whole working set fits the VMEM budget. Weight gradients are accumulated
in float32 across the batch grid: the TPU grid is sequential, so the
gradient output blocks are revisited by every grid step and updated in
place. Blocks that every grid step revisits (weights, ``M``, their
gradients) are held in VMEM once (``pl.Buffered(1)``), not double-buffered.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.butterfly import num_stages, stage_swap
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


def _stage_of(j, lo: int, hi: int, transpose: bool):
    """Stage applied at position ``j`` of the VPU stages ``[lo, hi)`` (Bᵀ
    applies the strides reversed)."""
    return hi - 1 - j if transpose else lo + j


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


def _apply_stages(buf, w_ref, lo: int, hi: int, transpose: bool) -> None:
    """The VPU stages ``[lo, hi)`` in place on ``buf``, in application
    order, as one loop."""
    def step(j, carry):
        _stage(buf, w_ref, _stage_of(j, lo, hi, transpose), transpose)
        return carry

    jax.lax.fori_loop(0, hi - lo, step, 0)


def _dot(a, b, contract=(1, 0), precision=None):
    """float32 MXU product contracting ``a``'s axis ``contract[0]`` with
    ``b``'s axis ``contract[1]`` (transposes ride the matmul, not a copy)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _mxu(a, b, contract=(1, 0)):
    """float32-faithful product (``precision=HIGHEST``): the fold block's."""
    return _dot(a, b, contract, jax.lax.Precision.HIGHEST)


def _for_groups(n: int, body) -> None:
    """``body(cols)`` for every 128-lane group of an ``n``-wide tile, as one
    loop."""
    def step(g, carry):
        body(_cols(g, _LANES))
        return carry

    jax.lax.fori_loop(0, n // _LANES, step, 0)


def _fold(buf, m_ref) -> None:
    """The folded stages in place on ``buf``: ``buf[:, g] = buf[:, g] @
    M[:, g]`` for every lane group ``g`` (:func:`fold_block`)."""
    def body(cols):
        buf[:, cols] = _mxu(buf[:, cols], m_ref[:, cols])
    _for_groups(buf.shape[-1], body)


def _fold_vjp(u_ref, gbuf, m_ref, dm_ref) -> None:
    """VJP of :func:`_fold` on one tile: ``u_ref`` holds the fold's input
    and ``gbuf`` its output's cotangent, which becomes the input's
    (``G·Mᵀ``); ``Uᵀ·G`` is added into ``dm_ref``, per lane group."""
    def body(cols):
        g = gbuf[:, cols]
        dm_ref[:, cols] += _mxu(u_ref[:, cols], g, (0, 0))
        gbuf[:, cols] = _mxu(g, m_ref[:, cols], (1, 1))
    _for_groups(gbuf.shape[-1], body)


def _butterfly_forward(buf, w_ref, m_ref, lo: int, hi: int,
                       transpose: bool) -> None:
    """The whole butterfly in place on the float32 tile ``buf``: the folded
    stages on the MXU (``m_ref``; None where the butterfly does not fold)
    and the VPU stages ``[lo, hi)`` — B folds first, Bᵀ last."""
    if m_ref is not None and not transpose:
        _fold(buf, m_ref)
    _apply_stages(buf, w_ref, lo, hi, transpose)
    if m_ref is not None and transpose:
        _fold(buf, m_ref)


def _butterfly_kernel(x_ref, w_ref, *refs, lo: int, hi: int,
                      transpose: bool):
    # stage math runs in float32: the TPU rotates only 32-bit lanes, and
    # v5e's VPU has no bfloat16 arithmetic anyway
    m_ref, o_ref, buf = refs if lo else (None, *refs)
    _copy(buf, x_ref)
    _butterfly_forward(buf, w_ref, m_ref, lo, hi, transpose)
    _copy(o_ref, buf)


def _butterfly_bwd_block(buf, gbuf, w_ref, lo: int, hi: int,
                         transpose: bool, segment: int, ckpt_ref, acts_ref,
                         dw_ref, top=None) -> None:
    """VJP of the VPU stages ``[lo, hi)`` on one tile, in place: ``buf``
    holds their input activation (clobbered), ``gbuf`` their output's
    cotangent, which becomes the input's; the tile's weight gradient is
    added into ``dw_ref``. ``top()``, if given, runs once ``buf`` holds the
    stages' output, before the dual sweep reads ``gbuf``: Bᵀ's folded
    stages come after its VPU stages.

    ``w_ref`` and ``dw_ref`` hold ``(2p, n)`` rows (:func:`_stage_weights`).
    Stage inputs come from segmented checkpointing: a forward sweep stores
    the activation entering position ``j`` for every segment boundary
    ``j ∈ {0, segment, 2·segment, …}`` in ``ckpt_ref``, then each segment
    is recomputed exactly once, into ``acts_ref``, during the reverse sweep
    — O(p') stage applications total instead of the O(p'²) full-prefix
    recompute. With ``top``, the forward sweep runs through the last
    segment too, to reach the output.

    The cotangent rule per stage is the *dual* stage applied to ``g``: the
    transpose of ``a ⊙ x + b ⊙ swap(x)`` is ``a ⊙ g + swap(b ⊙ g)`` and vice
    versa (swap is an involution).
    """
    stages = hi - lo
    if stages == 0:
        if top is not None:
            top()
        return
    seg = tuning.resolve_segment(stages, segment or None)
    n_seg = -(-stages // seg)

    def stage(j, carry):
        _stage(buf, w_ref, _stage_of(j, lo, hi, transpose), transpose)
        return carry

    # --- forward sweep: checkpoint the input of every segment ---
    def checkpoint(ci, carry):
        _copy(ckpt_ref.at[ci], buf)
        return jax.lax.fori_loop(ci * seg, jnp.minimum((ci + 1) * seg,
                                                       stages), stage, carry)

    if top is None:
        jax.lax.fori_loop(0, n_seg - 1, checkpoint, 0)
        _copy(ckpt_ref.at[n_seg - 1], buf)
    else:
        jax.lax.fori_loop(0, n_seg, checkpoint, 0)
        top()

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
            _stage(gbuf, w_ref, _stage_of(j, lo, hi, transpose), transpose,
                   t_ref=acts_ref.at[j - j0], dw_ref=dw_ref)
            return carry

        return jax.lax.fori_loop(0, j1 - j0, dual, carry)

    jax.lax.fori_loop(0, n_seg, segment_vjp, 0)


def _butterfly_vjp(buf, gbuf, x_ref, w_ref, m_ref, dw_ref, dm_ref, lo: int,
                   hi: int, transpose: bool, segment: int, ckpt_ref,
                   acts_ref) -> None:
    """VJP of the whole butterfly on one tile, in place: ``buf`` holds its
    input (clobbered; B, whose folded stages come first, reads it again
    from ``x_ref``), ``gbuf`` the output's cotangent, which becomes ``dx``.
    Weight gradients go into ``dw_ref`` (VPU stages) and ``dm_ref`` (the
    fold block; ``m_ref``/``dm_ref`` are None where the butterfly does not
    fold)."""
    folds = m_ref is not None
    if folds and not transpose:
        _fold(buf, m_ref)
    top = None
    if folds and transpose:
        def top():
            _fold_vjp(buf, gbuf, m_ref, dm_ref)
    _butterfly_bwd_block(buf, gbuf, w_ref, lo, hi, transpose, segment,
                         ckpt_ref, acts_ref, dw_ref, top)
    if folds and not transpose:
        _fold_vjp(x_ref, gbuf, m_ref, dm_ref)


def _zero_on_first_step(*refs) -> None:
    """Zero accumulator outputs on the first grid step: the TPU grid is
    sequential and every step revisits (and adds into) the same block.
    None entries (absent fold blocks) are skipped."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in refs:
            if r is not None:
                r[...] = jnp.zeros_like(r)


def _butterfly_bwd_kernel(x_ref, w_ref, g_ref, *refs, lo: int, hi: int,
                          transpose: bool, segment: int):
    if lo:
        m_ref, dx_ref, dw_ref, dm_ref, *scratch = refs
    else:
        m_ref = dm_ref = None
        dx_ref, dw_ref, *scratch = refs
    buf, gbuf, ckpt_ref, acts_ref = scratch
    _zero_on_first_step(dw_ref, dm_ref)
    _copy(buf, x_ref)
    _copy(gbuf, g_ref)
    _butterfly_vjp(buf, gbuf, x_ref, w_ref, m_ref, dw_ref, dm_ref, lo, hi,
                   transpose, segment, ckpt_ref, acts_ref)
    _copy(dx_ref, gbuf)


def tile_scratch(bb: int, n: int):
    """One float32 ``(bb, n)`` working tile in VMEM."""
    return pltpu.VMEM((bb, n), jnp.float32)


def checkpoint_scratch(stages: int, seg: int, bb: int, n: int):
    """VMEM scratch of the segmented backward: segment-boundary checkpoints
    and one segment's recomputed stage inputs, float32 (one tile each at
    least, so a butterfly whose stages all fold keeps the kernel's
    signature)."""
    return [pltpu.VMEM((max(1, len(range(0, stages, seg))), bb, n),
                       jnp.float32),
            pltpu.VMEM((seg, bb, n), jnp.float32)]


def resident(shape):
    """BlockSpec of a block every grid step revisits (weights, fold blocks
    and their gradients): held in VMEM once, not double-buffered."""
    return pl.BlockSpec(shape, lambda i: (0, 0),
                        pipeline_mode=pl.Buffered(1))


def fold_block(w: jnp.ndarray, transpose: bool) -> jnp.ndarray:
    """The folded stages of the ``(p, 2, n)`` butterfly ``w`` as a float32
    ``(128, n)`` array ``M``: column block ``g`` is the 128×128 matrix of
    stages s = 0…6 on lane group ``g``, so that ``x[:, g] @ M[:, g]``
    applies them. Built by running those stages, in the kernel's order and
    form, over 128 copies of the identity: ``a⊙x + b⊙swap(x)`` for s = 0…6
    (B applies them first), or ``a⊙x + swap(b⊙x)`` for s = 6…0 (Bᵀ applies
    them last). Costs about 21·128·n operations, whatever the batch."""
    n = w.shape[-1]
    m = jnp.tile(jnp.eye(_LANES, dtype=jnp.float32), (1, n // _LANES))
    w = w.astype(jnp.float32)
    order = range(tuning.FOLD_STAGES)
    for s in (reversed(order) if transpose else order):
        a, b = w[s, 0], w[s, 1]
        m = (a * m + stage_swap(b * m, 1 << s) if transpose
             else a * m + b * stage_swap(m, 1 << s))
    return m


def fold_weight_grads(w: jnp.ndarray, transpose: bool,
                      dm: jnp.ndarray) -> jnp.ndarray:
    """float32 ``(p, 2, n)`` gradient of the folded stages' weights: the VJP
    of :func:`fold_block` at the kernel's ``dM`` (zero past stage 6)."""
    _, vjp = jax.vjp(lambda w32: fold_block(w32, transpose),
                     w.astype(jnp.float32))
    return vjp(dm)[0]


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
    lo = tuning.folded_stages(n)
    block_b = tuning.resolve_block_b("butterfly", n, x.dtype, "fwd", block_b)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    in_specs = [pl.BlockSpec((bb, n), lambda i: (i, 0)),
                resident((_padded_rows(p), n))]
    args = [x2, weight_rows(w)]
    if lo:
        in_specs.append(resident((_LANES, n)))
        args.append(fold_block(w, transpose))
    out = pl.pallas_call(
        functools.partial(_butterfly_kernel, lo=lo, hi=num_stages(n),
                          transpose=transpose),
        name="butterfly_matmul_fwd",
        grid=(padded_b // bb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bb, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_b, n), x.dtype),
        scratch_shapes=[tile_scratch(bb, n)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(*args)
    return out[:b].reshape(*lead, n)


def _butterfly_bwd_call(x: jnp.ndarray, w: jnp.ndarray, g: jnp.ndarray,
                        transpose: bool, block_b, segment, interpret: bool):
    """``(dx, dw, dM)``: the VPU stages' float32 ``(p, 2, n)`` weight
    gradient and the fold block's (None where the butterfly does not
    fold)."""
    p, _, n = w.shape
    lo = tuning.folded_stages(n)
    block_b = tuning.resolve_block_b("butterfly", n, x.dtype, "bwd", block_b)
    seg = tuning.resolve_segment(p - lo, segment, kernel="butterfly", n=n,
                                 dtype=x.dtype)
    x2, lead, b, bb, padded_b = _flatten_batch(x, block_b)
    g2, _, _, _, _ = _flatten_batch(g.astype(x.dtype), block_b)
    tile = pl.BlockSpec((bb, n), lambda i: (i, 0))
    in_specs = [tile, resident((_padded_rows(p), n)), tile]
    out_specs = [tile, resident((_padded_rows(p), n))]
    out_shape = [jax.ShapeDtypeStruct((padded_b, n), x.dtype),
                 jax.ShapeDtypeStruct((_padded_rows(p), n), jnp.float32)]
    args = [x2, weight_rows(w), g2]
    if lo:
        in_specs.append(resident((_LANES, n)))
        out_specs.append(resident((_LANES, n)))
        out_shape.append(jax.ShapeDtypeStruct((_LANES, n), jnp.float32))
        args.append(fold_block(w, transpose))
    dx, dw, *dm = pl.pallas_call(
        functools.partial(_butterfly_bwd_kernel, lo=lo, hi=p,
                          transpose=transpose, segment=seg),
        name="butterfly_matmul_bwd",
        grid=(padded_b // bb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=([tile_scratch(bb, n), tile_scratch(bb, n)]
                        + checkpoint_scratch(p - lo, seg, bb, n)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tuning.vmem_limit_bytes()),
        interpret=interpret,
    )(*args)
    return dx[:b].reshape(*lead, n), weight_grads(dw, p), (dm or [None])[0]


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
    dx, dw, dm = _butterfly_bwd_call(x, w, g, transpose, block_b, segment,
                                     interpret)
    if dm is not None:
        dw = dw + fold_weight_grads(w, transpose, dm)
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
