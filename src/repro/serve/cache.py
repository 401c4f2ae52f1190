"""ONE cache interface for serving: dense and paged KV cache pools.

Every cache layout decision the engine, the tests and the benchmarks used
to make by reaching into raw nested cache dicts now goes through a
:class:`CachePool`:

* :class:`DenseCachePool` — the PR-5 layout: every decode slot owns a full
  ``max_len`` cache row (batch axis = slot index). Simple, exact, and the
  bisection baseline (``ServeEngine(..., pool="dense")``).
* :class:`PagedCachePool` — vLLM-style paged pool: full-attention KV leaves
  become ONE preallocated pool of fixed-size pages ``(num_pages,
  page_size, KV, D)`` plus a host-side per-slot page table and a FIFO
  free-list allocator with recycling. A slot's cache "row" is the logical
  concatenation of its pages; attention reads gather through the page
  table (:mod:`repro.kernels.paged_attention`), decode writes scatter into
  ``(page, offset)``. Capacity is reserved per *request* (``prompt +
  max_new_tokens``), not per worst-case ``max_len`` — which is why a paged
  engine sustains more concurrent slots than a dense one at equal memory.

Physical **page 0 is the trash page**: it is never allocated, every
unallocated page-table entry points at it, and the engine's pooled decode
step redirects inactive slots' whole page-table rows to it. Stray writes
(inactive lanes, right-pad tails) land there; reads from it are masked by
the positional validity mask, so its contents are never observable.

Layout rules (per block type, paged pool):

=========  =======================================================
attn/global/moe   ``self`` KV paged
xdec              ``self`` paged, ``cross`` dense (bounded enc_seq)
local             dense ring buffer (bounded at ``sliding_window``)
rec/mlstm/slstm   not pageable — sequential state; the engine keeps
                  these archs on the dense exact-length path
=========  =======================================================

The module also owns the per-block-type cache constructors that used to
live in :mod:`repro.models.lm` (``layer_cache_spec`` / ``init_layer_cache``
/ ``cache_specs`` / ``init_caches`` / ``write_cache_slot`` /
``reset_cache_slot``); ``lm`` keeps thin delegates so model-side callers
are unaffected.
"""

from __future__ import annotations

import collections
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import TRASH_PAGE
from repro.models import attention as attn
from repro.models import rglru as rgm
from repro.models import xlstm as xm

#: block types whose cache mixes positions sequentially (recurrent state)
#: — right-padded prefill or paged gather would corrupt them, so archs
#: containing them serve through the dense exact-length path.
SEQUENTIAL_STATE_BLOCKS = ("rec", "mlstm", "slstm")

#: per block type, the cache-dict keys whose KV moves into the page pool.
_PAGED_KEYS = {"attn": ("self",), "global": ("self",), "moe": ("self",),
               "xdec": ("self",)}


class PoolExhausted(RuntimeError):
    """The page pool cannot cover a requested allocation.

    Raised by :meth:`PagedCachePool.alloc_pages`; the serving engine
    catches it. At admission time the request stays queued until finished
    requests free pages; during incremental decode growth the engine
    preempts its youngest slot and recomputes it later — either way,
    exhaustion is backpressure, not a crash.
    """


def total_seq(cfg: ModelConfig, seq_len: int) -> int:
    """Cache length: text tokens plus any prepended frontend tokens."""
    return seq_len + (cfg.frontend_tokens if cfg.frontend == "vision" else 0)


def paged_supported(cfg: ModelConfig) -> bool:
    """True when every cache of ``cfg`` is pageable or boundedly dense."""
    types = set(cfg.block_unit) | set(cfg.tail_layers)
    return not (types & set(SEQUENTIAL_STATE_BLOCKS))


def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """True when prompts can be admitted as fixed-size prefill chunks.

    Chunked prefill runs the decode-style cached-attention path with C
    query positions at once, so it needs every self-attention cache to be
    paged (full attention, no sliding-window ring) and a plain token
    stream (no vision frontend prefix, no encoder)."""
    types = set(cfg.block_unit) | set(cfg.tail_layers)
    return (paged_supported(cfg)
            and not (types & {"local", "xdec", "enc"})
            and not cfg.frontend and not cfg.n_enc_layers)


# ---------------------------------------------------------------------------
# Per-block-type cache constructors (dense layout — the model-layer truth,
# delegated to by repro.models.lm)
# ---------------------------------------------------------------------------

def layer_cache_spec(cfg: ModelConfig, btype: str, batch: int,
                     seq_len: int) -> Optional[Dict]:
    if btype in ("attn", "global", "moe"):
        return {"self": attn.cache_spec(cfg, batch, seq_len)}
    if btype == "local":
        length = min(cfg.sliding_window, seq_len)
        return {"self": attn.cache_spec(cfg, batch, length)}
    if btype == "rec":
        return {"rec": rgm.rglru_cache_spec(cfg, batch)}
    if btype == "mlstm":
        return {"mlstm": xm.mlstm_cache_spec(cfg, batch)}
    if btype == "slstm":
        return {"slstm": xm.slstm_cache_spec(cfg, batch)}
    if btype == "xdec":
        return {"self": attn.cache_spec(cfg, batch, seq_len),
                "cross": attn.cache_spec(cfg, batch, cfg.enc_seq)}
    if btype == "enc":
        return None
    raise ValueError(btype)


def init_layer_cache(cfg: ModelConfig, btype: str, batch: int,
                     seq_len: int) -> Optional[Dict]:
    spec = layer_cache_spec(cfg, btype, batch, seq_len)
    if spec is None:
        return None
    cache = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                   spec)
    if btype == "mlstm":
        cache["mlstm"]["m"] = jnp.full(spec["mlstm"]["m"].shape, -1e30,
                                       jnp.float32)
    if btype == "slstm":
        cache["slstm"]["m"] = jnp.full(spec["slstm"]["m"].shape, -1e30,
                                       jnp.float32)
        cache["slstm"]["n"] = jnp.full(spec["slstm"]["n"].shape, 1e-6,
                                       jnp.float32)
    return cache


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Dict:
    unit = cfg.block_unit
    R = cfg.unit_repeats
    seq_len = total_seq(cfg, seq_len)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype), tree)

    return {
        "unit": [stack(layer_cache_spec(cfg, t, batch, seq_len))
                 for t in unit],
        "tail": [layer_cache_spec(cfg, t, batch, seq_len)
                 for t in cfg.tail_layers],
    }


def init_caches(cfg: ModelConfig, batch: int, seq_len: int) -> Dict:
    unit = cfg.block_unit
    R = cfg.unit_repeats
    seq_len = total_seq(cfg, seq_len)

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (R,) + a.shape).copy(), tree)

    return {
        "unit": [stack(init_layer_cache(cfg, t, batch, seq_len))
                 for t in unit],
        "tail": [init_layer_cache(cfg, t, batch, seq_len)
                 for t in cfg.tail_layers],
    }


def write_cache_slot(cfg: ModelConfig, pool: Dict, sub: Dict,
                     slot: jnp.ndarray) -> Dict:
    """Insert a batch-1 cache tree into batch index ``slot`` of a dense
    pool. Unit-stack leaves carry batch at axis 1 (axis 0 is the scan
    repeat), tail leaves at axis 0."""
    def upd(axis):
        def f(p, s):
            return jax.lax.dynamic_update_slice_in_dim(
                p, s.astype(p.dtype), slot, axis)
        return f

    return {
        "unit": jax.tree_util.tree_map(upd(1), pool["unit"], sub["unit"]),
        "tail": jax.tree_util.tree_map(upd(0), pool["tail"], sub["tail"]),
    }


def reset_cache_slot(cfg: ModelConfig, pool: Dict, slot: jnp.ndarray,
                     seq_len: int) -> Dict:
    """Reset batch index ``slot`` of a dense cache pool to its init state.
    ``seq_len`` must be the text length the pool was built with."""
    return write_cache_slot(cfg, pool, init_caches(cfg, 1, seq_len), slot)


# ---------------------------------------------------------------------------
# The pool interface
# ---------------------------------------------------------------------------

class CachePool:
    """Protocol every serving cache pool implements.

    Jittable tree transforms (``write_slot`` / ``reset_slot`` close over
    only static config; the engine wraps them in its CompileCache):

    * ``spec()`` / ``init()`` — the pool cache tree (shape-structs /
      zero-initialized arrays).
    * ``write_slot(caches, sub, slot, page_row)`` — splice a batch-1
      dense cache tree (a prefill result) into a slot.
    * ``reset_slot(caches, slot, page_row)`` — scrub a slot back to init.

    Host-side allocator lifecycle (pure Python, deterministic):

    * ``alloc_pages(slot, n_tokens)`` — ensure the slot can hold
      ``n_tokens`` cache positions; raises :class:`PoolExhausted`.
      Idempotent and *incremental*: growing an already-populated slot
      allocates only the missing pages, which is what the engine's
      incremental admission mode leans on.
    * ``free(slot)`` — return the slot's resources for recycling.
    * ``gather_args()`` — extra traced arguments the decode/chunk steps
      need (the page table for a paged pool; nothing for dense).

    ``faults`` optionally holds a :class:`repro.serve.faults.FaultInjector`
    (duck-typed to avoid an import cycle); a paged pool consults it on
    every real allocation attempt, so a seeded schedule can force
    exhaustion even while free pages exist.
    """

    kind: str = "none"
    faults = None                      # Optional[FaultInjector]

    def spec(self) -> Dict:
        raise NotImplementedError

    def init(self) -> Dict:
        raise NotImplementedError

    def write_slot(self, caches: Dict, sub: Dict, slot: jnp.ndarray,
                   page_row: Optional[jnp.ndarray] = None) -> Dict:
        raise NotImplementedError

    def reset_slot(self, caches: Dict, slot: jnp.ndarray,
                   page_row: Optional[jnp.ndarray] = None) -> Dict:
        raise NotImplementedError

    def alloc_pages(self, slot: int, n_tokens: int) -> None:
        return None

    def free(self, slot: int) -> None:
        return None

    def gather_args(self) -> Dict[str, jnp.ndarray]:
        return {}

    def page_row(self, slot: int) -> Optional[jnp.ndarray]:
        return None

    # -- introspection (metrics / tests) --------------------------------

    @property
    def pages_in_use(self) -> int:
        return 0

    @property
    def pages_hwm(self) -> int:
        return 0

    @property
    def total_pages(self) -> int:
        return 0

    def reset_stats(self) -> None:
        """Rebase high-water statistics to the current occupancy.

        ``engine.reset_metrics()`` calls this so bench warm-up artifacts
        (burn-in ``pages_hwm``) don't survive into the measured window.
        Live allocation state is untouched."""
        return None


class DenseCachePool(CachePool):
    """The PR-5 dense pooled cache: one full ``max_len`` row per slot."""

    kind = "dense"

    def __init__(self, cfg: ModelConfig, slots: int, max_len: int):
        self.cfg = cfg
        self.slots = slots
        self.max_len = int(max_len)

    def spec(self) -> Dict:
        return cache_specs(self.cfg, self.slots, self.max_len)

    def init(self) -> Dict:
        return init_caches(self.cfg, self.slots, self.max_len)

    def write_slot(self, caches, sub, slot, page_row=None):
        return write_cache_slot(self.cfg, caches, sub, slot)

    def reset_slot(self, caches, slot, page_row=None):
        return reset_cache_slot(self.cfg, caches, slot, self.max_len)

    def alloc_pages(self, slot: int, n_tokens: int) -> None:
        limit = total_seq(self.cfg, self.max_len)
        if n_tokens > limit:
            raise PoolExhausted(
                f"dense pool row holds {limit} positions, request needs "
                f"{n_tokens}")


class PagedCachePool(CachePool):
    """Fixed-size pages in one preallocated pool + per-slot page tables.

    ``num_pages`` counts *physical* pages including the trash page, so a
    pool holds ``(num_pages - 1) * page_size`` usable cache positions;
    the default matches a dense pool of the same ``slots``/``max_len``
    plus the trash page. The allocator itself is policy-free — it grows a
    slot to any requested coverage and raises :class:`PoolExhausted` when
    it cannot. The *engine* picks the reservation policy: eager admission
    reserves ``ceil((n_front + prompt + max_new) / page_size)`` pages up
    front (deadlock-free with no preemption path), incremental admission
    reserves only the prompt's pages and grows per decode tick, preempting
    on exhaustion. Either way the win over dense is that reservations
    track the *request*, not the engine-wide ``max_len``.

    The free list is a FIFO deque: pages allocate in ascending id order
    from a fresh pool and recycle in the order they were freed —
    deterministic, and stale page contents from a previous owner are
    unobservable (the new owner's validity mask only admits positions it
    has already written).
    """

    kind = "paged"

    def __init__(self, cfg: ModelConfig, slots: int, max_len: int, *,
                 page_size: int = 16, num_pages: Optional[int] = None):
        if not paged_supported(cfg):
            raise ValueError(
                f"{cfg.name}: sequential-state blocks "
                f"({SEQUENTIAL_STATE_BLOCKS}) cannot be paged; use "
                f"pool='dense'")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg = cfg
        self.slots = slots
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.max_len_total = total_seq(cfg, self.max_len)
        self.pages_per_slot = math.ceil(self.max_len_total / self.page_size)
        if num_pages is None:
            num_pages = slots * self.pages_per_slot + 1
        if num_pages < 2:
            raise ValueError(f"num_pages must be >= 2 (page 0 is the "
                             f"trash page), got {num_pages}")
        self.num_pages = int(num_pages)
        self._free: collections.deque = collections.deque(
            range(1, self.num_pages))
        self._owned: List[List[int]] = [[] for _ in range(slots)]
        self._table = np.full((slots, self.pages_per_slot), TRASH_PAGE,
                              np.int32)
        self._hwm = 0

    # -- allocator ------------------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        return math.ceil(n_tokens / self.page_size)

    def alloc_pages(self, slot: int, n_tokens: int) -> None:
        """Ensure ``slot`` owns pages covering positions [0, n_tokens)."""
        if n_tokens > self.max_len_total:
            raise PoolExhausted(
                f"slot page table holds {self.max_len_total} positions, "
                f"request needs {n_tokens}")
        owned = self._owned[slot]
        need = self.pages_for(n_tokens) - len(owned)
        if need <= 0:
            return
        if self.faults is not None:
            self.faults.check("pool.alloc")
        if need > len(self._free):
            raise PoolExhausted(
                f"pool has {len(self._free)} free pages, slot {slot} "
                f"needs {need} more (of {self.num_pages - 1} usable)")
        for _ in range(need):
            page = self._free.popleft()
            self._table[slot, len(owned)] = page
            owned.append(page)
        self._hwm = max(self._hwm, self.pages_in_use)

    def free(self, slot: int) -> None:
        """Recycle the slot's pages (FIFO) and trash its table row."""
        self._free.extend(self._owned[slot])
        self._owned[slot] = []
        self._table[slot, :] = TRASH_PAGE

    # Both hand jax a private copy of the table: jax may alias a numpy
    # buffer (the CPU does when it is 64-byte aligned) or read it after
    # returning (an asynchronous host-to-device copy), and the allocator
    # rewrites the table (grow, free) while a dispatched step is in flight.
    def gather_args(self) -> Dict[str, jnp.ndarray]:
        return {"page_table": jnp.asarray(self._table.copy())}

    def page_row(self, slot: int) -> jnp.ndarray:
        return jnp.asarray(self._table[slot].copy())

    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def pages_hwm(self) -> int:
        return self._hwm

    @property
    def total_pages(self) -> int:
        return self.num_pages

    def reset_stats(self) -> None:
        self._hwm = self.pages_in_use

    def free_list(self) -> Tuple[int, ...]:
        """Snapshot of the free list (allocation order) — test surface."""
        return tuple(self._free)

    def slot_pages(self, slot: int) -> Tuple[int, ...]:
        """Physical pages ``slot`` currently owns, in logical order — test
        surface for incremental growth / preemption accounting."""
        return tuple(self._owned[slot])

    # -- cache tree -----------------------------------------------------

    def _paged_keys(self, btype: str) -> Tuple[str, ...]:
        return _PAGED_KEYS.get(btype, ())

    def _layer_spec(self, btype: str) -> Optional[Dict]:
        spec = layer_cache_spec(self.cfg, btype, self.slots,
                                self.max_len_total)
        if spec is None:
            return None
        KV, D = self.cfg.n_kv_heads, self.cfg.head_dim_
        pool_shape = (self.num_pages, self.page_size, KV, D)
        out = dict(spec)
        for key in self._paged_keys(btype):
            out[key] = {t: jax.ShapeDtypeStruct(pool_shape, s.dtype)
                        for t, s in spec[key].items()}
        return out

    def spec(self) -> Dict:
        unit = self.cfg.block_unit
        R = self.cfg.unit_repeats

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((R,) + s.shape, s.dtype),
                tree)

        return {
            "unit": [stack(self._layer_spec(t)) for t in unit],
            "tail": [self._layer_spec(t) for t in self.cfg.tail_layers],
        }

    def init(self) -> Dict:
        # every pageable leaf inits to zeros; dense leaves of pageable
        # archs (local rings, xdec cross) do too, so plain zeros is exact
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.spec())

    # -- slot surgery ---------------------------------------------------

    def _map_layers(self, caches: Dict, sub: Dict, paged_fn, dense_fn
                    ) -> Dict:
        """Apply ``paged_fn(pool_leaf, sub_leaf, stacked)`` to paged
        leaves and ``dense_fn(pool_leaf, sub_leaf, stacked)`` to dense
        ones, per block type, preserving the tree structure."""
        cfg = self.cfg

        def one(btype: str, pool_layer, sub_layer, stacked: bool):
            if pool_layer is None:
                return None
            pkeys = self._paged_keys(btype)
            out = {}
            for key, leafs in pool_layer.items():
                fn = paged_fn if key in pkeys else dense_fn
                out[key] = jax.tree_util.tree_map(
                    lambda p, s: fn(p, s, stacked), leafs, sub_layer[key])
            return out

        return {
            "unit": [one(t, caches["unit"][i], sub["unit"][i], True)
                     for i, t in enumerate(cfg.block_unit)],
            "tail": [one(t, caches["tail"][i], sub["tail"][i], False)
                     for i, t in enumerate(cfg.tail_layers)],
        }

    def write_slot(self, caches: Dict, sub: Dict, slot: jnp.ndarray,
                   page_row: Optional[jnp.ndarray] = None) -> Dict:
        """Scatter a batch-1 *dense* cache tree (a whole-prompt prefill at
        ``max_len``) into the slot's pages; dense leaves splice at the
        slot's batch index exactly like the dense pool. Positions beyond
        the slot's allocated pages route to the trash page via the
        ``page_row`` sentinel entries."""
        ps = self.page_size
        L = self.max_len_total
        pos = jnp.arange(L)
        pages = page_row[pos // ps]
        offs = pos % ps

        def paged(p, s, stacked):
            if stacked:                # (R, N, ps, KV, D) <- (R, 1, L, ...)
                return p.at[:, pages, offs].set(s[:, 0].astype(p.dtype))
            return p.at[pages, offs].set(s[0].astype(p.dtype))

        def dense(p, s, stacked):
            return jax.lax.dynamic_update_slice_in_dim(
                p, s.astype(p.dtype), slot, 1 if stacked else 0)

        return self._map_layers(caches, sub, paged, dense)

    def reset_slot(self, caches: Dict, slot: jnp.ndarray,
                   page_row: Optional[jnp.ndarray] = None) -> Dict:
        return self.write_slot(
            caches, init_caches(self.cfg, 1, self.max_len), slot, page_row)


def make_pool(cfg: ModelConfig, slots: int, max_len: int, *,
              kind: str = "paged", page_size: int = 16,
              num_pages: Optional[int] = None) -> CachePool:
    """Pool factory: ``kind`` "paged" (falls back to dense for
    sequential-state archs) or "dense" (always available, for
    bisection)."""
    if kind == "dense":
        return DenseCachePool(cfg, slots, max_len)
    if kind == "paged":
        if not paged_supported(cfg):
            return DenseCachePool(cfg, slots, max_len)
        return PagedCachePool(cfg, slots, max_len, page_size=page_size,
                              num_pages=num_pages)
    raise ValueError(f"unknown pool kind {kind!r}: expected 'paged' or "
                     f"'dense'")
