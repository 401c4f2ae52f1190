"""Flash-attention Pallas kernels (TPU target), forward *and* backward —
beyond-paper optimization.

The jnp blockwise path in :mod:`repro.models.attention` implements the same
online-softmax algorithm but XLA materializes each (block_q, block_kv) score
tile and the f32 accumulator in HBM between loop steps (visible in the
roofline memory term). The forward kernel keeps q-tile, running
max/denominator and the accumulator resident in VMEM for the whole KV sweep:
HBM traffic drops to one read of Q/K/V + one write of O (+ the (B·H, S, 1)
logsumexp row, the only residual the backward needs).

Training support: ``flash_attention`` carries a :func:`jax.custom_vjp` with
**checkpointed recompute** in the same spirit as the butterfly kernels — the
O(S²) probability matrix is never stored; backward re-derives each score
tile from (q, k, lse) inside VMEM. Two fused kernels cover the three
cotangents (the standard flash backward split):

* dKV kernel, grid (B·H, S/block_kv): for each kv tile, sweep the valid q
  range accumulating ``dv += pᵀ·do`` and ``dk += dsᵀ·q`` in float32;
* dQ kernel, grid (B·H, S/block_q): for each q tile, sweep the valid kv
  range accumulating ``dq += ds·k``;

with ``p = exp(s − lse)`` and ``ds = p ⊙ (dp − Δ)``, ``Δ = rowsum(do ⊙ o)``
computed once outside (elementwise, XLA-fused). Causal + sliding-window
masking mirrors the forward exactly. Block sizes default to the
:mod:`repro.kernels.tuning` VMEM model.

Validated against :func:`repro.kernels.ref.flash_attention_ref` — forward
and gradients — in interpret mode (tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning

NEG_INF = -1e30


def _kv_bounds(qi, block_q: int, block_kv: int, seq_len: int, causal: bool,
               window: int):
    """KV-block sweep range for one q block (mirrors the masking)."""
    nkv = seq_len // block_kv
    if causal:
        hi = (qi * block_q + block_q + block_kv - 1) // block_kv
    else:
        hi = nkv
    if window > 0:
        lo = jnp.maximum(0, (qi * block_q - window) // block_kv)
    else:
        lo = 0
    return lo, hi


def _tile_mask(q0, k0, block_q: int, block_kv: int, causal: bool,
               window: int):
    """(block_q, block_kv) validity of the score tile whose first query and
    key positions are ``q0`` and ``k0``."""
    q_ids = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= k_ids <= q_ids
    if window > 0:
        mask &= k_ids > q_ids - window
    return mask


def _mm(a, b, contract=(1, 0)):
    """float32 product contracting ``a``'s axis ``contract[0]`` with ``b``'s
    axis ``contract[1]`` (transposed operands ride the MXU, not a copy)."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((), ())),
                               preferred_element_type=jnp.float32)


# Per-row softmax statistics (running max, denominator, logsumexp, Δ) are
# (rows, 1) columns throughout: Mosaic lays out 2-D values only, and the
# (B·H, S, 1) lse/Δ arrays give BlockSpecs whose last dim is the full one.

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q: int,
                  block_kv: int, seq_len: int, causal: bool, window: int,
                  scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
    lo, hi = _kv_bounds(qi, block_q, block_kv, seq_len, causal, window)

    def body(j, state):
        m, l, acc = state
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = _mm(q, k, (1, 1))                           # (bq, bkv)
        s = jnp.where(_tile_mask(qi * block_q, j * block_kv, block_q,
                                 block_kv, causal, window), s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + _mm(p, v)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_q: int, block_kv: int,
                         seq_len: int, causal: bool, window: int,
                         scale: float):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
    do = do_ref[...].astype(jnp.float32)                # (bq, d)
    lse = lse_ref[...]                                  # (bq, 1)
    delta = delta_ref[...]                              # (bq, 1)
    lo, hi = _kv_bounds(qi, block_q, block_kv, seq_len, causal, window)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_kv, block_kv), :].astype(jnp.float32)
        s = _mm(q, k, (1, 1))
        mask = _tile_mask(qi * block_q, j * block_kv, block_q, block_kv,
                          causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = _mm(do, v, (1, 1))                         # (bq, bkv)
        ds = p * (dp - delta)
        return dq + _mm(ds, k)
    dq = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, block_kv: int,
                          seq_len: int, causal: bool, window: int,
                          scale: float):
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)                  # (bkv, d)
    v = v_ref[...].astype(jnp.float32)
    nq = seq_len // block_q
    # valid q blocks: q >= min(k) when causal; q < max(k) + window when
    # windowed (the exact per-element mask is applied inside the tile)
    lo = (ki * block_kv) // block_q if causal else 0
    if window > 0:
        hi = jnp.minimum(nq,
                         (ki * block_kv + block_kv - 1 + window) // block_q
                         + 1)
    else:
        hi = nq

    def body(j, state):
        dk, dv = state
        rows = pl.ds(j * block_q, block_q)
        q = q_ref[rows, :].astype(jnp.float32) * scale
        do = do_ref[rows, :].astype(jnp.float32)
        lse = lse_ref[rows, :]                          # (bq, 1)
        delta = delta_ref[rows, :]
        s = _mm(q, k, (1, 1))                           # (bq, bkv)
        mask = _tile_mask(j * block_q, ki * block_kv, block_q, block_kv,
                          causal, window)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + _mm(p, do, (0, 0))                    # (bkv, d)
        dp = _mm(do, v, (1, 1))                         # (bq, bkv)
        ds = p * (dp - delta)
        dk = dk + _mm(ds, q, (0, 0))                    # (bkv, d), q scaled
        return dk, dv

    z = jnp.zeros((block_kv, k_ref.shape[-1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (z, z))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_fwd_call(q, k, v, causal, window, block_q, block_kv, interpret,
                    *, with_lse: bool):
    B, H, S, D = q.shape
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    scale = D ** -0.5
    grid = (B * H, S // block_q)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q,
                          block_kv=block_kv, seq_len=S, causal=causal,
                          window=window, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(B, H, S, D)
    return (out, lse) if with_lse else out


def _flash_bwd_call(q, k, v, out, lse, g, causal, window, block_q, block_kv,
                    interpret):
    B, H, S, D = q.shape
    scale = D ** -0.5
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    dof = g.astype(q.dtype).reshape(B * H, S, D)
    # Δ = rowsum(dO ⊙ O): elementwise over (B·H, S, D), XLA fuses it — the
    # only O(S·D) extra HBM pass the backward needs.
    delta = jnp.sum(dof.astype(jnp.float32)
                    * out.reshape(B * H, S, D).astype(jnp.float32), axis=-1,
                    keepdims=True)
    kw = dict(block_q=block_q, block_kv=block_kv, seq_len=S, causal=causal,
              window=window, scale=scale)
    row_specs = [
        pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),   # q
        pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),   # k
        pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),   # v
        pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),   # do
        pl.BlockSpec((None, S, 1), lambda b, i: (b, 0, 0)),   # lse
        pl.BlockSpec((None, S, 1), lambda b, i: (b, 0, 0)),   # delta
    ]
    dq_specs = list(row_specs)
    dq_specs[0] = pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0))
    dq_specs[3] = pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0))
    dq_specs[4] = pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0))
    dq_specs[5] = pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kw),
        grid=(B * H, S // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    dkv_specs = list(row_specs)
    dkv_specs[1] = pl.BlockSpec((None, block_kv, D), lambda b, i: (b, i, 0))
    dkv_specs[2] = pl.BlockSpec((None, block_kv, D), lambda b, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kw),
        grid=(B * H, S // block_kv),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((None, block_kv, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_kv, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    shape = (B, H, S, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_diff(q, k, v, causal, window, block_q, block_kv, interpret):
    return _flash_fwd_call(q, k, v, causal, window, block_q, block_kv,
                           interpret, with_lse=False)


def _flash_diff_fwd(q, k, v, causal, window, block_q, block_kv, interpret):
    out, lse = _flash_fwd_call(q, k, v, causal, window, block_q, block_kv,
                               interpret, with_lse=True)
    # residuals: inputs + output + the (B·H, S, 1) logsumexp — the score matrix
    # is recomputed tile-by-tile in VMEM, never stored
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, window, block_q, block_kv, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_call(q, k, v, out, lse, g, causal, window, block_q,
                           block_kv, interpret)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q=None, block_kv=None,
                    interpret: bool = False) -> jnp.ndarray:
    """q/k/v: (B, H, S, D) (KV heads pre-expanded or H == KV). S must be a
    multiple of the block sizes.

    Differentiable in q, k, v via fused Pallas backward kernels (custom_vjp)
    that recompute score tiles from the saved logsumexp instead of storing
    the O(S²) probability matrix. ``block_q``/``block_kv`` default to the
    :mod:`repro.kernels.tuning` VMEM model; pass ints only to override.
    """
    from repro.obs.profiling import annotate
    B, H, S, D = q.shape
    if block_q is None or block_kv is None:
        bq, bkv = tuning.flash_blocks(S, D, jnp.dtype(q.dtype).name, "bwd")
        block_q = block_q or bq
        block_kv = block_kv or bkv
    with annotate("flash_attention"):
        return _flash_diff(q, k, v, causal, window, block_q, block_kv,
                           interpret)
