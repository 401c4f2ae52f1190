"""Paged-gather decode attention: jnp oracle + Pallas TPU kernel.

The serving engine's :class:`repro.serve.cache.PagedCachePool` stores KV in
one physical pool of fixed-size pages, ``(num_pages, page_size, KV, D)``,
with a per-slot page table mapping logical page ``j`` (absolute positions
``[j*page_size, (j+1)*page_size)``) to a physical page id. Decode reads a
slot's KV through that indirection.

Two implementations, numerically interchangeable:

* :func:`paged_attend_ref` — the jnp gather oracle: materialize the
  logical view ``pool[page_table]`` (B, P·page_size, KV, D) and run plain
  masked GQA attention in f32. This is what XLA executes on CPU and what
  every parity test measures against; it supports ``Sq >= 1`` query
  positions, which is how chunked prefill reuses the decode path.
* :func:`_paged_decode_pallas` — the Pallas kernel (single-query decode):
  grid ``(B, P)`` with the page table and per-slot positions as **scalar
  prefetch** operands, so each KV BlockSpec's ``index_map`` reads the
  physical page id straight from the prefetched table — the gather never
  materializes, HBM traffic is one read of the *live* pages only (pages
  past ``cur_pos`` are skipped via ``pl.when``), and the online-softmax
  state (m, l, acc) stays in VMEM scratch across the page sweep.

Like the flash kernels, the Pallas path is validated in interpret mode on
CPU (``backend="pallas_interpret"``); Mosaic compilation on real TPUs is
part of the standing TPU-validation item in ROADMAP.md.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# physical page 0 is reserved: never handed out by the allocator, the
# target of every unmapped page-table entry and every out-of-range scatter.
# Its contents are garbage by design — the positional validity mask
# (kpos <= q_pos) keeps it unobservable.
TRASH_PAGE = 0


def gather_pages(pool: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """Logical per-slot KV view: (N, ps, KV, D) + (B, P) -> (B, P·ps, KV, D).

    Logical position ``p`` of row ``b`` lives at
    ``pool[page_table[b, p // ps], p % ps]`` — i.e. gathered order IS
    absolute-position order, which is what lets the validity mask below be
    a plain ``kpos <= q_pos``.
    """
    B, P = page_table.shape
    _, ps, KV, D = pool.shape
    return pool[page_table].reshape(B, P * ps, KV, D)


def paged_attend_ref(q: jnp.ndarray, k_pool: jnp.ndarray,
                     v_pool: jnp.ndarray, page_table: jnp.ndarray,
                     q_pos: jnp.ndarray) -> jnp.ndarray:
    """jnp gather oracle. q: (B, Sq, KV, G, D) grouped-query layout;
    pools: (N, ps, KV, D); page_table: (B, P) int32; q_pos: (B, Sq)
    absolute positions of the queries. Returns (B, Sq, KV, G, D).

    Causal over absolute positions: query at position ``t`` attends to
    every cached position ``<= t``. Entries beyond a slot's written prefix
    (trash-page garbage, recycled-page leftovers, right-pad tails) all sit
    at positions ``> t`` by the pool's allocation invariant, so the single
    mask keeps them inert.
    """
    B, Sq, KV, G, D = q.shape
    ka = gather_pages(k_pool, page_table).astype(q.dtype)
    va = gather_pages(v_pool, page_table).astype(q.dtype)
    L = ka.shape[1]
    scale = D ** -0.5
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, ka
                        ).astype(jnp.float32) * scale
    kpos = jnp.arange(L)
    valid = kpos[None, None, :] <= q_pos[:, :, None]      # (B, Sq, L)
    logits = jnp.where(valid[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(va.dtype), va)


# ---------------------------------------------------------------------------
# Pallas kernel (single-query decode)
# ---------------------------------------------------------------------------

def _decode_kernel(pt_ref, cp_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size: int,
                   pages_per_slot: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    cur = cp_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages strictly past the written prefix contribute nothing: skip the
    # FLOPs (the DMA for their block still lands, but on the trash page /
    # a stale page, both inert)
    @pl.when(p * page_size <= cur)
    def _attend():
        q = q_ref[0].astype(jnp.float32)               # (KV, G, D)
        k = k_ref[0].astype(jnp.float32)               # (ps, KV, D)
        v = v_ref[0].astype(jnp.float32)
        D = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)        # (KV, G, ps)
        s = s * (D ** -0.5)
        ids = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        s = jnp.where(ids <= cur, s, NEG_INF)
        m_prev = m_ref[...]                            # (KV, G)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        pexp = jnp.exp(s - m_new[..., None])           # (KV, G, ps)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + pexp.sum(axis=-1)
        pv = jax.lax.dot_general(
            pexp, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)        # (KV, G, D)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(p == pages_per_slot - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[..., None]).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pool, v_pool, page_table, cur_pos, *,
                         interpret: bool) -> jnp.ndarray:
    B, KV, G, D = q.shape
    N, ps, _, _ = k_pool.shape
    P = page_table.shape[1]
    kernel = functools.partial(_decode_kernel, page_size=ps,
                               pages_per_slot=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, KV, G, D), lambda b, p, pt, cp: (b, 0, 0, 0)),
            # the paged gather: the physical page id comes straight from
            # the scalar-prefetched page table
            pl.BlockSpec((1, ps, KV, D),
                         lambda b, p, pt, cp: (pt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, KV, D),
                         lambda b, p, pt, cp: (pt[b, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, D),
                               lambda b, p, pt, cp: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G), jnp.float32),          # running max
            pltpu.VMEM((KV, G), jnp.float32),          # running denom
            pltpu.VMEM((KV, G, D), jnp.float32),       # accumulator
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(page_table, cur_pos, q, k_pool, v_pool)


def _sharded_paged_decode(q, k_pool, v_pool, page_table, cur_pos, *, mesh,
                          axes, interpret: bool) -> jnp.ndarray:
    """The Pallas kernel under ``shard_map``: slots split over the mesh's
    data ``axes`` (zero-padded to a multiple of the shard count — a padded
    slot reads the trash page), the page pools replicated. A Mosaic kernel
    cannot be partitioned by XLA, so a mesh-sharded engine needs this."""
    from jax.sharding import PartitionSpec as P

    from repro.runtime import butterfly_sharding as bsh
    B = q.shape[0]
    pad = -B % bsh.shard_count(mesh, axes)
    if pad:
        q = jnp.pad(q, ((0, pad),) + ((0, 0),) * (q.ndim - 1))
        page_table = jnp.pad(page_table, ((0, pad), (0, 0)),
                             constant_values=TRASH_PAGE)
        cur_pos = jnp.pad(cur_pos, (0, pad))
    out = bsh.shard_map_compat(
        functools.partial(_paged_decode_pallas, interpret=interpret),
        mesh=mesh, in_specs=(P(axes), P(), P(), P(axes), P(axes)),
        out_specs=P(axes))(q, k_pool, v_pool, page_table, cur_pos)
    return out[:B]


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           cur_pos: jnp.ndarray, *,
                           backend: Optional[str] = None) -> jnp.ndarray:
    """Single-query paged decode attention. q: (B, KV, G, D); pools
    (N, ps, KV, D); page_table (B, P); cur_pos (B,) absolute positions.

    ``backend=None`` resolves from the ambient
    :class:`~repro.kernels.context.ExecutionContext` (jnp oracle on CPU,
    Pallas on TPU, ``pallas_interpret`` under the test contexts). Under an
    ambient context with a mesh the kernel runs slot-sharded over its data
    axes (:func:`_sharded_paged_decode`).
    """
    from repro.kernels import context as exctx
    from repro.obs.profiling import annotate
    ctx = exctx.current_execution()
    if backend is None:
        backend = exctx.resolve_backend(ctx.backend if ctx else "auto")
    with annotate("paged_attention"):
        if backend == "jnp":
            out = paged_attend_ref(q[:, None], k_pool, v_pool, page_table,
                                   cur_pos[:, None])
            return out[:, 0]
        cur_pos = jnp.asarray(cur_pos, jnp.int32)
        interpret = backend == "pallas_interpret"
        if ctx is not None and ctx.mesh is not None:
            from repro.runtime import butterfly_sharding as bsh
            axes = bsh.data_axes(ctx.mesh, ctx.mesh_axes)
            if axes:
                return _sharded_paged_decode(
                    q, k_pool, v_pool, page_table, cur_pos, mesh=ctx.mesh,
                    axes=axes, interpret=interpret)
        return _paged_decode_pallas(q, k_pool, v_pool, page_table, cur_pos,
                                    interpret=interpret)
