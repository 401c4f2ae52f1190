"""`repro.serve.cache`: the CachePool API and the paged page allocator.

The allocator is pure host-side Python, so these tests are exact and fast:
deterministic FIFO alloc/free/recycle order, typed
:class:`~repro.serve.cache.PoolExhausted` backpressure, trash-page
invariants on the table, and a full randomized trace replay proving no
page is ever leaked or double-owned.
"""

import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.paged_attention import TRASH_PAGE
from repro.serve import cache as cache_lib
from repro.serve.cache import (DenseCachePool, PagedCachePool, PoolExhausted,
                               make_pool)

ARCH = "smollm-135m-smoke"


@pytest.fixture(scope="module")
def cfg():
    return registry.get(ARCH)


def _pool(cfg, slots=4, max_len=64, page_size=8, num_pages=None):
    return PagedCachePool(cfg, slots, max_len, page_size=page_size,
                          num_pages=num_pages)


# ---------------------------------------------------------------------------
# Allocator determinism
# ---------------------------------------------------------------------------

def test_pages_allocate_in_ascending_order_from_fresh_pool(cfg):
    pool = _pool(cfg)                      # default: 4*8+1 = 33 pages
    assert pool.total_pages == 4 * 8 + 1
    assert pool.free_list() == tuple(range(1, 33))    # page 0 reserved
    pool.alloc_pages(0, 20)                # ceil(20/8) = 3 pages
    assert list(pool._table[0, :3]) == [1, 2, 3]
    assert (pool._table[0, 3:] == TRASH_PAGE).all()
    pool.alloc_pages(1, 1)
    assert pool._table[1, 0] == 4
    assert pool.pages_in_use == 4 and pool.pages_hwm == 4


def test_alloc_is_incremental_growth(cfg):
    """alloc_pages(slot, n) tops the slot up to cover n positions — the
    engine calls it once with the whole budget, but growth is legal and
    never re-allocates already-owned pages."""
    pool = _pool(cfg)
    pool.alloc_pages(0, 8)                 # 1 page
    pool.alloc_pages(0, 9)                 # +1 page
    pool.alloc_pages(0, 9)                 # no-op
    assert list(pool._table[0, :2]) == [1, 2] and pool.pages_in_use == 2


def test_free_recycles_fifo(cfg):
    """Pages recycle in the order they were freed, so two replays of the
    same trace produce identical page tables — determinism the parity
    tests implicitly rely on."""
    pool = _pool(cfg, num_pages=7)         # 6 usable
    pool.alloc_pages(0, 16)                # pages 1, 2
    pool.alloc_pages(1, 16)                # pages 3, 4
    pool.free(0)                           # free list: 5, 6, 1, 2
    assert pool.free_list() == (5, 6, 1, 2)
    pool.alloc_pages(2, 24)                # pages 5, 6, 1
    assert list(pool._table[2, :3]) == [5, 6, 1]
    assert (pool._table[0] == TRASH_PAGE).all()
    assert pool.pages_hwm == 5             # 3 + the earlier HWM of 4 -> 5


def test_replay_determinism(cfg):
    def run():
        pool = _pool(cfg, num_pages=9)
        tables = []
        pool.alloc_pages(0, 10)
        pool.alloc_pages(1, 20)
        pool.free(0)
        pool.alloc_pages(2, 30)
        tables.append(pool._table.copy())
        pool.free(1)
        pool.alloc_pages(3, 12)
        tables.append(pool._table.copy())
        return tables, pool.free_list()
    a, fa = run()
    b, fb = run()
    assert fa == fb
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# Typed backpressure
# ---------------------------------------------------------------------------

def test_device_table_snapshots_survive_free(cfg):
    """Regression: ``page_row``/``gather_args`` handed jax the live numpy
    table, which jax on the CPU aliases when the buffer is 64-byte aligned;
    ``free()`` then rewrote a dispatched scrub's page row to trash, and the
    request's KV survived in its recycled pages. Many pools, so some rows
    land on an aligned address."""
    for slots in range(1, 33):
        pool = _pool(cfg, slots=slots, max_len=16, page_size=4)
        for s in range(slots):
            pool.alloc_pages(s, 16)
        want = pool._table.copy()
        rows = [pool.page_row(s) for s in range(slots)]
        table = pool.gather_args()["page_table"]
        for s in range(slots):
            pool.free(s)
        assert (np.asarray(table) == want).all()
        for s, row in enumerate(rows):
            assert (np.asarray(row) == want[s]).all(), (slots, s)


def test_pool_exhausted_is_typed_and_non_destructive(cfg):
    pool = _pool(cfg, num_pages=4)         # 3 usable
    pool.alloc_pages(0, 16)                # 2 pages
    before = (pool.free_list(), pool._table.copy())
    with pytest.raises(PoolExhausted, match="free pages"):
        pool.alloc_pages(1, 16)            # needs 2, only 1 left
    # a failed allocation must not consume pages or touch any table row
    assert pool.free_list() == before[0]
    np.testing.assert_array_equal(pool._table, before[1])
    assert isinstance(PoolExhausted("x"), RuntimeError)


def test_over_table_request_raises_even_with_free_pages(cfg):
    pool = _pool(cfg, slots=2, max_len=16, page_size=8, num_pages=64)
    with pytest.raises(PoolExhausted, match="positions"):
        pool.alloc_pages(0, 17)            # table row holds ceil(16/8)=2


def test_dense_pool_budget_check(cfg):
    pool = DenseCachePool(cfg, slots=2, max_len=32)
    pool.alloc_pages(0, 32)                # fits: no-op
    with pytest.raises(PoolExhausted, match="positions"):
        pool.alloc_pages(0, 33)


# ---------------------------------------------------------------------------
# No leaks across a full randomized trace replay
# ---------------------------------------------------------------------------

def test_no_page_leaked_or_double_owned_across_trace(cfg):
    """Randomized admission/finish trace: after every event, the owned
    sets are disjoint, owned + free covers exactly the usable pages, and
    every table entry matches ownership; after the final drain the free
    list holds every usable page exactly once."""
    pool = _pool(cfg, slots=4, max_len=64, page_size=8, num_pages=17)
    rng = np.random.default_rng(0)
    live = {}

    def check():
        owned = [p for pages in pool._owned for p in pages]
        assert len(owned) == len(set(owned)), "double-owned page"
        assert TRASH_PAGE not in owned
        universe = set(range(1, pool.total_pages))
        assert set(owned) | set(pool.free_list()) == universe
        assert len(owned) + len(pool.free_list()) == len(universe)
        for s in range(4):
            row = pool._table[s]
            assert list(row[:len(pool._owned[s])]) == pool._owned[s]
            assert (row[len(pool._owned[s]):] == TRASH_PAGE).all()

    for _ in range(200):
        if live and (len(live) == 4 or rng.random() < 0.5):
            slot = rng.choice(sorted(live))
            pool.free(int(slot))
            del live[slot]
        else:
            slot = next(s for s in range(4) if s not in live)
            try:
                pool.alloc_pages(slot, int(rng.integers(1, 65)))
                live[slot] = True
            except PoolExhausted:
                pass                       # backpressure, state untouched
        check()
    for slot in sorted(live):
        pool.free(int(slot))
    check()
    assert pool.pages_in_use == 0
    assert sorted(pool.free_list()) == list(range(1, pool.total_pages))


# ---------------------------------------------------------------------------
# Geometry, factory, capability predicates
# ---------------------------------------------------------------------------

def test_pool_geometry_and_pages_for(cfg):
    pool = _pool(cfg, slots=3, max_len=20, page_size=8)
    assert pool.pages_per_slot == 3        # ceil(20/8)
    assert pool.total_pages == 3 * 3 + 1   # + trash page
    assert [pool.pages_for(n) for n in (1, 8, 9, 16, 17)] == [1, 1, 2, 2, 3]
    with pytest.raises(ValueError, match="page_size"):
        _pool(cfg, page_size=0)
    with pytest.raises(ValueError, match="num_pages"):
        _pool(cfg, num_pages=1)            # the trash page alone is not a pool


def test_make_pool_factory_and_fallbacks(cfg):
    assert make_pool(cfg, 2, 32, kind="paged").kind == "paged"
    assert make_pool(cfg, 2, 32, kind="dense").kind == "dense"
    # sequential-state archs silently fall back to dense under "paged"
    rcfg = registry.get("recurrentgemma-2b-smoke")
    assert make_pool(rcfg, 2, 32, kind="paged").kind == "dense"
    with pytest.raises(ValueError, match="rec"):
        PagedCachePool(rcfg, 2, 32)
    with pytest.raises(ValueError, match="pool kind"):
        make_pool(cfg, 2, 32, kind="ring")


def test_capability_predicates():
    assert cache_lib.paged_supported(registry.get(ARCH))
    assert cache_lib.chunked_prefill_supported(registry.get(ARCH))
    rcfg = registry.get("recurrentgemma-2b-smoke")
    assert not cache_lib.paged_supported(rcfg)
    assert not cache_lib.chunked_prefill_supported(rcfg)


def test_paged_spec_pools_kv_leaves(cfg):
    """Paged spec: self-attention KV leaves become ONE (num_pages, ps, KV,
    D) pool shared across slots (plus the unit-repeat stack axis), while
    the dense spec keeps per-slot max_len rows."""
    pool = _pool(cfg, slots=4, max_len=64, page_size=8)
    spec = pool.spec()
    k = spec["unit"][0]["self"]["k"]
    R = cfg.unit_repeats
    assert k.shape == (R, pool.total_pages, 8, cfg.n_kv_heads,
                       cfg.head_dim_)
    dk = DenseCachePool(cfg, 4, 64).spec()["unit"][0]["self"]["k"]
    assert dk.shape == (R, 4, 64, cfg.n_kv_heads, cfg.head_dim_)
