"""`repro.kernels.paged_attention`: the paged-gather decode attention.

Gates the two implementations against each other and against plain dense
attention: the jnp gather oracle must equal dense masked attention on a
page-permuted pool (scatter/gather roundtrip + positional mask), trash-page
and stale-page contents must be unobservable, multi-query (chunk) calls
must agree with single-query calls, and the Pallas kernel (interpret mode
on CPU, like the flash kernels) must match the oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import context as exctx
from repro.kernels import paged_attention as pa

B, P, PS, KV, G, D = 2, 3, 4, 2, 2, 8   # P*PS = 12 logical positions
N = 1 + B * P                            # physical pages incl. trash


def _setup(seed=0, dtype=jnp.float32):
    """Random per-slot dense K/V scattered into a permuted page pool."""
    rng = np.random.default_rng(seed)
    L = P * PS
    k = rng.normal(size=(B, L, KV, D)).astype(np.float32)
    v = rng.normal(size=(B, L, KV, D)).astype(np.float32)
    # physical ids 1..N-1 in a seeded shuffle: page order must not matter
    ids = rng.permutation(np.arange(1, N)).reshape(B, P).astype(np.int32)
    k_pool = np.zeros((N, PS, KV, D), np.float32)
    v_pool = np.zeros((N, PS, KV, D), np.float32)
    for b in range(B):
        for p in range(P):
            k_pool[ids[b, p]] = k[b, p * PS:(p + 1) * PS]
            v_pool[ids[b, p]] = v[b, p * PS:(p + 1) * PS]
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype),
            jnp.asarray(ids))


def _dense_ref(q, k, v, q_pos):
    """Plain masked GQA attention over the dense (B, L, KV, D) layout."""
    L = k.shape[1]
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k
                        ).astype(jnp.float32) * (D ** -0.5)
    valid = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    logits = jnp.where(valid[:, None, None, :, :], logits, pa.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)


def test_gather_pages_restores_position_order():
    k, _, k_pool, _, ids = _setup()
    np.testing.assert_array_equal(np.asarray(pa.gather_pages(k_pool, ids)),
                                  np.asarray(k))


def test_oracle_matches_dense_attention_on_permuted_pool():
    k, v, k_pool, v_pool, ids = _setup()
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    for pos in (0, 3, 7, 11):              # page-boundary and interior
        q_pos = jnp.full((B, 1), pos, jnp.int32)
        got = pa.paged_attend_ref(q, k_pool, v_pool, ids, q_pos)
        want = _dense_ref(q, k, v, q_pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)


def test_trash_and_stale_pages_are_unobservable():
    """Garbage in the trash page, in unmapped table entries, and in cache
    positions past ``q_pos`` must never reach the output — the positional
    validity mask is the only thing standing between them and the softmax,
    so this is THE paging-safety gate."""
    k, v, k_pool, v_pool, ids = _setup()
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, 1, KV, G, D)), jnp.float32)
    q_pos = jnp.asarray([[5], [2]], jnp.int32)    # mid-page prefixes
    want = pa.paged_attend_ref(q, k_pool, v_pool, ids, q_pos)

    k_dirty, v_dirty = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    k_dirty[pa.TRASH_PAGE] = 1e4                   # trash-page garbage
    v_dirty[pa.TRASH_PAGE] = -1e4
    for b in range(B):                             # beyond-prefix garbage
        pos = int(q_pos[b, 0])
        page, off = (pos + 1) // PS, (pos + 1) % PS
        k_dirty[int(ids[b, page]), off:] = 7e3
        v_dirty[int(ids[b, page]), off:] = -7e3
    ids_dirty = np.asarray(ids).copy()
    got = pa.paged_attend_ref(q, jnp.asarray(k_dirty),
                              jnp.asarray(v_dirty),
                              jnp.asarray(ids_dirty), q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_chunk_queries_match_single_queries():
    """Sq>1 (the chunked-prefill read path) must agree with Sq=1 per
    position — chunking a prompt is a pure batching decision."""
    _, _, k_pool, v_pool, ids = _setup(seed=3)
    rng = np.random.default_rng(4)
    Sq = 4
    q = jnp.asarray(rng.normal(size=(B, Sq, KV, G, D)), jnp.float32)
    base = 5
    q_pos = base + jnp.tile(jnp.arange(Sq)[None, :], (B, 1))
    chunk = pa.paged_attend_ref(q, k_pool, v_pool, ids, q_pos)
    for s in range(Sq):
        single = pa.paged_attend_ref(q[:, s:s + 1], k_pool, v_pool, ids,
                                     q_pos[:, s:s + 1])
        np.testing.assert_allclose(np.asarray(chunk[:, s]),
                                   np.asarray(single[:, 0]),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_interpret_matches_oracle(dtype):
    _, _, k_pool, v_pool, ids = _setup(seed=5, dtype=dtype)
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), dtype)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    for pos in (0, 4, 11):
        cur = jnp.full((B,), pos, jnp.int32)
        want = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur,
                                         backend="jnp")
        got = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur,
                                        backend="pallas_interpret")
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol)


def test_pallas_skips_pages_past_the_prefix():
    """Pages wholly past ``cur_pos`` are skipped by ``pl.when`` — NaN
    garbage there must not poison the online softmax (a mask applied after
    the dot product would still propagate NaN through exp; the skip must
    be structural)."""
    _, _, k_pool, v_pool, ids = _setup(seed=7)
    k_dirty = np.asarray(k_pool).copy()
    v_dirty = np.asarray(v_pool).copy()
    # slot 0's LAST page is beyond cur_pos=3: fill it with NaN
    k_dirty[int(ids[0, 2])] = np.nan
    v_dirty[int(ids[0, 2])] = np.nan
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), jnp.float32)
    cur = jnp.asarray([3, 11], jnp.int32)
    got = pa.paged_decode_attention(jnp.asarray(q), jnp.asarray(k_dirty),
                                    jnp.asarray(v_dirty), ids, cur,
                                    backend="pallas_interpret")
    assert np.isfinite(np.asarray(got)).all()
    want = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur,
                                     backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_backend_resolves_from_ambient_context():
    """backend=None inside ``use_execution(pallas_interpret)`` runs the
    kernel path; the result still matches the oracle."""
    _, _, k_pool, v_pool, ids = _setup(seed=9)
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), jnp.float32)
    cur = jnp.asarray([7, 9], jnp.int32)
    want = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur,
                                     backend="jnp")
    with exctx.use_execution(
            exctx.ExecutionContext(backend="pallas_interpret")):
        got = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


def test_kernel_under_a_mesh_runs_slot_sharded():
    """Under an ambient context with a data mesh the kernel runs inside
    ``shard_map`` (XLA cannot partition a Mosaic kernel): slots split over
    the 8 devices, 2 slots zero-padded to 8 onto the trash page, and the
    result matches the oracle."""
    _, _, k_pool, v_pool, ids = _setup(seed=11)
    rng = np.random.default_rng(12)
    q = jnp.asarray(rng.normal(size=(B, KV, G, D)), jnp.float32)
    cur = jnp.asarray([5, 10], jnp.int32)
    want = pa.paged_decode_attention(q, k_pool, v_pool, ids, cur,
                                     backend="jnp")
    ctx = exctx.resolve_execution(exctx.ExecutionContext(
        backend="pallas_interpret", mesh_shape=(8,)))
    with exctx.use_execution(ctx):
        jaxpr = jax.make_jaxpr(pa.paged_decode_attention)(
            q, k_pool, v_pool, ids, cur)
        got = jax.jit(pa.paged_decode_attention)(q, k_pool, v_pool, ids,
                                                 cur)
    assert "shard_map" in str(jaxpr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)
