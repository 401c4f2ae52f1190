"""The serving subsystem (`repro.serve`): continuous batching, cache-pool
admission (paged + dense), compile discipline, oracle parity, checkpoint
restore.

The acceptance properties of the engine:

(a) **continuous batching** — a short request admitted after a long one
    finishes first, and its freed slot is refilled from the queue while the
    long request keeps decoding (tick-indexed, so machine speed is
    irrelevant);
(b) **compile discipline** — chunked prefill on the default paged pool
    traces ONE prefill for every prompt length; the dense pool's bucketed
    prefill traces exactly once per (bucket, context) — both gated by the
    engine's CompileCache trace counter;
(c) **oracle parity** — greedy engine outputs equal the single-request
    ``prefill`` + ``decode_step`` oracle per request, on BOTH pool kinds,
    independent of co-batched neighbors (this also proves the page-table
    gather, the chunked prefill split, and the per-slot vector-``cur_pos``
    decode are exact);
(d) **paged capacity** — at equal cache memory, a paged pool sustains
    strictly more concurrent slots than dense, and exhaustion defers
    admission (backpressure) instead of crashing;
(e) **preemptible incremental admission** — a request preempted mid-decode
    (pages freed, requeued, prefix recomputed) resumes to greedy output
    token-identical to the same request on an idle engine, and at equal
    memory incremental admission co-runs a mixed trace that eager
    admission must serialize.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.kernels.context import ExecutionContext
from repro.models import lm
from repro.serve import (GREEDY, Request, SamplingParams, ServeClient,
                         ServeEngine, loader, sample_logits)

ARCH = "smollm-135m-smoke"


@pytest.fixture(scope="module")
def cfg():
    return registry.get(ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return loader.init_params(cfg, seed=0)


def _prompt(rng, cfg, n):
    return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)


def _req(prompt, max_new=4, **kw):
    return Request(prompt=prompt, max_new_tokens=max_new, **kw)


def _oracle_generate(cfg, params, prompt, max_new, max_len):
    """Single-request greedy reference: exact-length prefill + scalar-pos
    decode loop (the pre-engine serving path)."""
    caches = lm.init_caches(cfg, 1, max_len)
    logits, caches = lm.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])}, caches)
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(max_new - 1):
        logits, caches = lm.decode_step(
            cfg, params, jnp.asarray([toks[-1]], jnp.int32), caches,
            jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
        pos += 1
    return toks


# ---------------------------------------------------------------------------
# Sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def test_param_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=-1.0)
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)
        assert GREEDY.greedy and not SamplingParams(temperature=0.7).greedy

    def test_greedy_is_argmax_and_ignores_rng(self):
        logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 1.0]])
        got = sample_logits(logits, None, GREEDY)
        np.testing.assert_array_equal(np.asarray(got), [1, 0])

    def test_top_k_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64, jnp.float32)
        sp = SamplingParams(temperature=1.0, top_k=2)
        keys = jax.random.split(jax.random.PRNGKey(0), 16)
        toks = np.concatenate([
            np.asarray(sample_logits(logits, k, sp)) for k in keys])
        assert set(toks.tolist()) <= {2, 3}

    def test_top_k_at_or_above_vocab_is_disabled(self):
        """`top_k >= V` means "no restriction" — it must sample, not crash
        (jax.lax.top_k requires k <= V), and match top_k=0 exactly."""
        logits = jax.random.normal(jax.random.PRNGKey(4), (8, 4))
        key = jax.random.PRNGKey(5)
        for k in (4, 10):
            got = sample_logits(logits, key,
                                SamplingParams(temperature=1.0, top_k=k))
            want = sample_logits(logits, key,
                                 SamplingParams(temperature=1.0, top_k=0))
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))

    def test_top_p_keeps_nucleus_only(self):
        # one dominant token: p=0.5 nucleus is exactly {3}
        logits = jnp.asarray([[0.0, 0.0, 0.0, 10.0]] * 32, jnp.float32)
        sp = SamplingParams(temperature=1.0, top_p=0.5)
        toks = np.asarray(sample_logits(logits, jax.random.PRNGKey(1), sp))
        assert set(toks.tolist()) == {3}

    def test_stochastic_is_jittable_and_plausible(self):
        sp = SamplingParams(temperature=1.0, top_k=3, top_p=0.9)
        fn = jax.jit(lambda lg, k: sample_logits(lg, k, sp))
        logits = jax.random.normal(jax.random.PRNGKey(2), (8, 32))
        toks = np.asarray(fn(logits, jax.random.PRNGKey(3)))
        assert toks.shape == (8,) and (0 <= toks).all() and (toks < 32).all()


# ---------------------------------------------------------------------------
# (a) continuous batching: slot refill without stalling in-flight requests
# ---------------------------------------------------------------------------

def test_continuous_batching_refills_freed_slot(cfg, params):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0)
    rng = np.random.default_rng(0)
    fa = eng.submit(_req(_prompt(rng, cfg, 6), max_new=12))    # long
    fb = eng.submit(_req(_prompt(rng, cfg, 5), max_new=3))     # short
    fc = eng.submit(_req(_prompt(rng, cfg, 7), max_new=3))     # queued
    eng.run_until_idle()
    a, b, c = fa.result(0).metrics, fb.result(0).metrics, fc.result(0).metrics

    # A and B were co-batched from tick 0; C had to queue behind them
    assert a.admit_tick == 0 and b.admit_tick == 0
    assert c.admit_tick > b.admit_tick
    # the short request finished first and its slot was handed to C on the
    # NEXT tick — while A was still decoding (no stall, no re-batch barrier)
    assert b.finish_tick < a.finish_tick
    assert c.admit_tick == b.finish_tick + 1
    assert c.finish_tick < a.finish_tick
    # the long request never stalled: a single-chunk prompt admits, samples
    # its first token AND takes that tick's decode in the admission tick
    # (two tokens), then one token per tick — the dense engine's exact
    # arithmetic, preserved by chunked admission for prompts <= one chunk
    assert a.finish_tick - a.admit_tick == a.new_tokens - 2
    assert [len(f.result(0).tokens) for f in (fa, fb, fc)] == [12, 3, 3]


def test_stop_token_frees_slot_early(cfg, params):
    eng = ServeEngine(cfg, params, slots=1, max_len=64, seed=0)
    rng = np.random.default_rng(1)
    prompt = _prompt(rng, cfg, 5)
    # oracle-known second token becomes the stop token
    want = _oracle_generate(cfg, params, prompt, 4, 64)
    fut = eng.submit(_req(prompt, max_new=16, stop_token=want[1]))
    eng.run_until_idle()
    assert fut.result(0).tokens == want[:2]


# ---------------------------------------------------------------------------
# (b) compile discipline
# ---------------------------------------------------------------------------

def test_chunked_prefill_compiles_once_for_all_lengths(cfg, params):
    """The paged default: prompts spanning one, two, and three chunks all
    share ONE chunk-prefill trace — there are no per-bucket prefills."""
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0)
    assert eng.pool.kind == "paged" and eng.prefill_chunk == 16
    rng = np.random.default_rng(2)
    futs = [eng.submit(_req(_prompt(rng, cfg, n), max_new=2))
            for n in (5, 7, 20, 3, 40)]
    eng.run_until_idle()
    for f in futs:
        f.result(0)
    traces = eng.compile_stats["traces"]
    assert not any(k[0] == "prefill" for k in traces), traces
    assert traces[("chunk_prefill", cfg.name, 2, 16, eng.ctx)] == 1
    assert traces[("decode", cfg.name, 2, "paged", GREEDY, eng.ctx)] == 1
    # chunk prefill + pooled decode + first-token sample: three compiles
    # serve every prompt length the engine will ever see
    assert eng.compile_stats["compiles"] == 3


def test_bucketed_prefill_compiles_once_per_bucket(cfg, params):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0,
                      pool="dense")
    rng = np.random.default_rng(2)
    futs = [eng.submit(_req(_prompt(rng, cfg, n), max_new=2))
            for n in (5, 7, 8, 3, 6)]      # all land in the 8-bucket
    eng.run_until_idle()
    for f in futs:
        f.result(0)
    traces = eng.compile_stats["traces"]
    prefills = {k: v for k, v in traces.items() if k[0] == "prefill"}
    assert list(prefills.values()) == [1], prefills
    ((_, _, bucket, batch, ctx),) = prefills.keys()
    assert (bucket, batch) == (8, 1) and isinstance(ctx, ExecutionContext)

    # a longer prompt opens exactly one new bucket; everything else stays
    eng.submit(_req(_prompt(rng, cfg, 20), max_new=2))
    eng.run_until_idle()
    prefills = {k: v for k, v in eng.compile_stats["traces"].items()
                if k[0] == "prefill"}
    assert sorted(k[2] for k in prefills) == [8, 32]
    assert all(v == 1 for v in prefills.values())
    # the pooled decode step and the cache-splice each traced once, ever
    assert eng.compile_stats["traces"][
        ("decode", cfg.name, 2, "dense", GREEDY, eng.ctx)] == 1
    assert eng.compile_stats["traces"][
        ("insert", cfg.name, 2, "dense", eng.ctx)] == 1


def test_exact_buckets_for_sequential_state_archs():
    rcfg = registry.get("recurrentgemma-2b-smoke")
    eng = ServeEngine(rcfg, loader.init_params(rcfg, seed=0), slots=1,
                      max_len=64)
    # padding would fold into the RG-LRU state / ring buffer: exact
    # lengths, and the paged default silently falls back to a dense pool
    assert eng.pool.kind == "dense" and eng.prefill_chunk is None
    assert eng.bucket_for(5) == 5 and eng.bucket_for(13) == 13
    scfg = registry.get(ARCH)
    eng2 = ServeEngine(scfg, loader.init_params(scfg, seed=0), slots=1,
                       max_len=64)
    assert eng2.pool.kind == "paged"
    assert eng2.bucket_for(5) == 8 and eng2.bucket_for(13) == 16


def test_sequential_state_arch_serves_end_to_end():
    """The exact-bucket admission path actually serves: RG-LRU recurrent
    state + sliding-window ring buffers through the engine, with prompts
    BOTH below and above the window (below-window prefill exercises the
    short-prompt ring path in attention.py), matching the single-request
    oracle token-for-token."""
    cfg = registry.get("recurrentgemma-2b-smoke")
    assert cfg.sliding_window == 16
    params = loader.init_params(cfg, seed=0)
    rng = np.random.default_rng(7)
    prompts = [_prompt(rng, cfg, 5), _prompt(rng, cfg, 20)]
    eng = ServeEngine(cfg, params, slots=2, max_len=48, seed=0)
    futs = [eng.submit(_req(p, max_new=4)) for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        assert f.result(0).tokens == _oracle_generate(cfg, params, p, 4, 48)


def test_client_driver_crash_fails_futures():
    """A tick that raises must not strand futures on a dead driver thread:
    every queued/in-flight future resolves with the real error and the
    client refuses new submissions."""
    cfg = registry.get(ARCH)
    eng = ServeEngine(cfg, loader.init_params(cfg, seed=0), slots=1,
                      max_len=64)

    def boom():
        raise RuntimeError("tick exploded")
    eng.step = boom
    with ServeClient(eng) as client:
        futs = [client.submit(_req([1, 2, 3])) for _ in range(2)]
        with pytest.raises(RuntimeError, match="tick exploded"):
            futs[0].result(timeout=30)
        with pytest.raises(RuntimeError, match="tick exploded"):
            futs[1].result(timeout=30)
        # the abort path ran, so the client is marked closed: further
        # submissions are refused loudly instead of queueing forever
        with pytest.raises(RuntimeError, match="closed"):
            client.submit(_req([1], max_new=1))
    assert not eng.metrics.requests        # aborted records were evicted
    assert eng.pool.pages_in_use == 0      # aborted slots freed their pages


# ---------------------------------------------------------------------------
# (c) oracle parity: pool layout and co-batching never change tokens
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool", ["paged", "dense"])
def test_engine_matches_single_request_oracle(cfg, params, pool):
    """Requests of different lengths through 2 slots (so admission order,
    co-batching neighbors, and slot refill all differ per request) must
    reproduce the single-request oracle token-for-token — on the paged
    pool (where the 20-token prompt spans TWO prefill chunks, gating the
    chunked split + page-table gather) and on dense."""
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, cfg, n) for n in (5, 9, 20)]
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0, pool=pool)
    assert eng.pool.kind == pool
    if pool == "paged":
        assert prompts[2].size > eng.prefill_chunk   # multi-chunk coverage
    futs = [eng.submit(_req(p, max_new=6)) for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        want = _oracle_generate(cfg, params, p, 6, 64)
        assert f.result(0).tokens == want
    assert eng.pool.pages_in_use == 0      # every page recycled on finish


def test_scrubbed_slots_do_not_change_outputs(cfg, params):
    """reset_slot hygiene between requests is a no-op for results — on the
    paged pool this scrubs through the slot's page row before the pages
    are recycled."""
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, cfg, n) for n in (4, 11, 6, 8)]
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0,
                      scrub_freed_slots=True)
    futs = [eng.submit(_req(p, max_new=5)) for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        assert f.result(0).tokens == _oracle_generate(cfg, params, p, 5, 64)


def _page_content(eng, pages):
    """Concatenated flat content of physical ``pages`` across every paged
    cache leaf of the engine's pool tree."""
    import jax as _jax
    P = eng.pool.total_pages
    out = []
    for leaf in _jax.tree_util.tree_leaves(eng._caches):
        if leaf.ndim >= 2 and leaf.shape[0] == P:
            out.append(np.asarray(leaf[list(pages)]).ravel())
        elif leaf.ndim >= 3 and leaf.shape[1] == P:
            out.append(np.asarray(leaf[:, list(pages)]).ravel())
    assert out, "no paged leaves found"
    return np.concatenate(out)


@pytest.mark.parametrize("exit_path", ["cancel", "deadline", "preempt"])
def test_lifecycle_exits_scrub_freed_pages(cfg, params, exit_path):
    """Regression: cancel/deadline/preempt used to call ``pool.free``
    WITHOUT the ``scrub_freed_slots`` re-init that ``_finish`` performs,
    so a dead request's KV survived in recycled pages. All exits now run
    the shared scrub-then-free tail: the freed pages read back zero."""
    kw = dict(slots=1, max_len=64, seed=0, pool="paged",
              scrub_freed_slots=True)
    if exit_path == "preempt":
        kw.update(admission="incremental")
    eng = ServeEngine(cfg, params, **kw)
    rng = np.random.default_rng(31)
    fut = eng.submit(_req(_prompt(rng, cfg, 6), max_new=16,
                          deadline_ticks=(4 if exit_path == "deadline"
                                          else None)))
    for _ in range(3):                     # prefill + a few decode ticks
        eng.step()
    pages = eng.pool.slot_pages(0)
    assert pages and np.abs(_page_content(eng, pages)).max() > 0

    if exit_path == "cancel":
        rid = eng.active_requests()[0]
        assert eng.cancel(rid)
        eng.step()
        with pytest.raises(Exception, match="cancelled"):
            fut.result(0)
    elif exit_path == "deadline":
        while not fut.done():              # ticks reach deadline_ticks=4
            eng.step()
        with pytest.raises(Exception, match="deadline"):
            fut.result(0)
    else:
        eng._preempt(0)                    # white-box: the page-kick path

    assert eng.pool.slot_pages(0) == ()
    assert np.abs(_page_content(eng, pages)).max() == 0, \
        f"{exit_path} leaked KV content into recycled pages"
    if exit_path == "preempt":             # resumed run still exact
        eng.run_until_idle()
        want = _oracle_generate(cfg, params, fut.result(0).prompt, 16, 64)
        assert fut.result(0).tokens == want


def test_preempt_resume_metrics_survive(cfg, params):
    """Regression: a resumed (post-preemption) request's recompute used to
    re-fire ``on_prefill_done`` (inflating ``prefills``) and would have
    reset ``new_tokens``/TTFT through ``on_first_token`` on the bucketed
    path. After a preempt-and-resume cycle every counter must reflect the
    request's real life: one prefill each, every generated token counted
    once, TTFT from the FIRST admission."""
    rng = np.random.default_rng(32)
    prompts = [_prompt(rng, cfg, 5) for _ in range(2)]
    eng = ServeEngine(cfg, params, slots=2, max_len=32, seed=0,
                      pool="paged", page_size=8, num_pages=5,
                      prefill_chunk=4, admission="incremental")
    futs = [eng.submit(_req(p, max_new=14)) for p in prompts]
    eng.run_until_idle()
    results = [f.result(0) for f in futs]
    snap = eng.metrics.snapshot()
    assert snap["preempted"] >= 1          # the cycle actually happened
    assert snap["prefills"] == 2           # recompute is NOT a new prefill
    for r in results:
        assert r.metrics.new_tokens == 14  # preserved across the cycle
        assert len(r.tokens) == 14
        assert r.metrics.ttft > 0
        assert r.metrics.ttft <= r.metrics.latency


def test_percentile_is_ceil_based_nearest_rank():
    """Pin `_percentile` to the explicit ceil-based nearest-rank
    convention (rank `ceil(q*n)`, 1-based): Python's `round()` (banker's
    rounding) used to pick the lower rank inconsistently on even-length
    windows."""
    from repro.serve.metrics import _percentile
    assert _percentile([], 0.5) == 0.0
    assert _percentile([7.0], 0.5) == 7.0
    assert _percentile([7.0], 0.95) == 7.0
    # n=2: p50 -> rank ceil(1.0)=1 (lower median); p95 -> rank 2
    assert _percentile([1.0, 2.0], 0.50) == 1.0
    assert _percentile([1.0, 2.0], 0.95) == 2.0
    # n=3: p50 -> rank ceil(1.5)=2 (true median); p95 -> rank 3
    assert _percentile([1.0, 2.0, 3.0], 0.50) == 2.0
    assert _percentile([1.0, 2.0, 3.0], 0.95) == 3.0
    # n=20: p50 -> rank 10; p95 -> rank 19; extremes clamp to the sample
    vals = [float(i) for i in range(1, 21)]
    assert _percentile(vals, 0.50) == 10.0
    assert _percentile(vals, 0.95) == 19.0
    assert _percentile(vals, 0.0) == 1.0
    assert _percentile(vals, 1.0) == 20.0


def test_async_client_resolves_futures(cfg, params):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, seed=0)
    rng = np.random.default_rng(5)
    prompts = [_prompt(rng, cfg, n) for n in (5, 9)]
    with ServeClient(eng) as client:
        futs = [client.submit(_req(p)) for p in prompts]
        results = [f.result(timeout=300) for f in futs]
    for p, r in zip(prompts, results):
        assert r.tokens == _oracle_generate(cfg, params, p, 4, 64)
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == 2
    assert snap["total_tokens"] == 8
    assert snap["pool"]["kind"] == "paged"
    assert snap["pool"]["pages_in_use"] == 0
    assert snap["pool"]["pages_hwm"] > 0


def test_submit_validation(cfg, params):
    eng = ServeEngine(cfg, params, slots=1, max_len=16)
    # the removed positional form breaks loudly with the migration spelled
    # out, through the engine and the client alike
    with pytest.raises(TypeError, match="repro.serve.Request"):
        eng.submit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(TypeError, match="repro.serve.Request"):
        eng.submit(_req([1, 2, 3]), 4)
    with ServeClient(eng) as client:
        with pytest.raises(TypeError, match="repro.serve.Request"):
            client.submit([1, 2, 3], max_new_tokens=4)
    # Request validates its own fields at construction
    with pytest.raises(ValueError, match="empty"):
        Request(prompt=[], max_new_tokens=2)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt=[1, 2], max_new_tokens=0)
    # engine-dependent checks stay at submit time
    with pytest.raises(ValueError, match="budget"):
        eng.submit(_req(np.arange(10), max_new=10))
    with pytest.raises(ValueError, match="sampling"):
        eng.submit(_req([1, 2], sampling=SamplingParams(temperature=0.7)))
    # an explicit rid collides with an in-flight request
    f = eng.submit(_req([1, 2], max_new=1, rid=7))
    with pytest.raises(ValueError, match="rid 7"):
        eng.submit(_req([3, 4], max_new=1, rid=7))
    eng.run_until_idle()
    f.result(0)


def test_request_is_frozen_and_normalized():
    r = Request(prompt=np.asarray([[1, 2], [3, 4]]), max_new_tokens=2)
    assert r.prompt == (1, 2, 3, 4)        # any int array-like flattens
    assert all(isinstance(t, int) for t in r.prompt)
    with pytest.raises(AttributeError):
        r.max_new_tokens = 5


# ---------------------------------------------------------------------------
# (d) paged capacity: more concurrency at equal memory, typed backpressure
# ---------------------------------------------------------------------------

def test_paged_sustains_more_slots_than_dense_at_equal_memory(cfg, params):
    """Equal KV memory — dense 2 slots x 48 rows = 96 positions vs paged
    12 usable pages x 8 = 96 positions — but the paged engine reserves per
    *request* budget (11 tokens -> 2 pages), so it runs 4 requests
    concurrently where dense can only ever co-batch 2. Outputs stay
    oracle-exact and every page drains back to the free list."""
    rng = np.random.default_rng(8)
    prompts = [_prompt(rng, cfg, 5) for _ in range(4)]
    want = [_oracle_generate(cfg, params, p, 6, 48) for p in prompts]

    dense = ServeEngine(cfg, params, slots=2, max_len=48, seed=0,
                        pool="dense")
    dfuts = [dense.submit(_req(p, max_new=6)) for p in prompts]
    dense.run_until_idle()

    paged = ServeEngine(cfg, params, slots=4, max_len=48, seed=0,
                        pool="paged", page_size=8, num_pages=13)
    assert (paged.pool.total_pages - 1) * paged.pool.page_size \
        == dense.slots * dense.max_len
    pfuts = [paged.submit(_req(p, max_new=6)) for p in prompts]
    paged.run_until_idle()

    for w, df, pf in zip(want, dfuts, pfuts):
        assert df.result(0).tokens == w
        assert pf.result(0).tokens == w
    dsnap, psnap = dense.metrics.snapshot(), paged.metrics.snapshot()
    assert dsnap["max_concurrent_slots"] == 2
    assert psnap["max_concurrent_slots"] == 4
    assert psnap["max_concurrent_slots"] > dsnap["max_concurrent_slots"]
    # 4 concurrent requests x 2 pages, all recycled after the drain
    assert psnap["pool"]["pages_hwm"] == 8
    assert paged.pool.pages_in_use == 0
    assert len(paged.pool.free_list()) == paged.pool.total_pages - 1


def test_pool_exhaustion_defers_admission(cfg, params):
    """A pool too small for every queued request admits what fits, counts
    the exhaustion, keeps the rest queued FIFO, and finishes everything
    once finished requests recycle their pages — backpressure, no crash,
    no token drift."""
    rng = np.random.default_rng(9)
    prompts = [_prompt(rng, cfg, 5) for _ in range(3)]
    # 4 usable pages x 8 = 32 positions; each request reserves 2 pages, so
    # only two of the four slots can ever be occupied at once
    eng = ServeEngine(cfg, params, slots=4, max_len=48, seed=0,
                      pool="paged", page_size=8, num_pages=5)
    futs = [eng.submit(_req(p, max_new=6)) for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        assert f.result(0).tokens == _oracle_generate(cfg, params, p, 6, 48)
    snap = eng.metrics.snapshot()
    assert snap["max_concurrent_slots"] == 2       # pages, not slots, bind
    assert snap["pool"]["exhausted_events"] > 0
    assert snap["pool"]["pages_hwm"] == 4
    assert eng.pool.pages_in_use == 0
    # a request that could NEVER fit is rejected at submit, not queued
    with pytest.raises(ValueError, match="pages"):
        eng.submit(_req(_prompt(rng, cfg, 40), max_new=2))


# ---------------------------------------------------------------------------
# (e) preemptible incremental admission: preempt/recompute parity, overload
# ---------------------------------------------------------------------------

def test_preempted_request_resumes_token_identical(cfg, params):
    """The tentpole parity gate. Two 5-token prompts, 14 new tokens each,
    on 4 usable 8-token pages: both full budgets (3 pages each) cannot
    co-reside, so as decode grows page tables the younger request is
    preempted — pages freed, request requeued with its generated prefix,
    recomputed via chunked prefill. Greedy decoding makes the resumed
    output token-identical to the single-request oracle (and hence to the
    never-preempted run)."""
    rng = np.random.default_rng(11)
    prompts = [_prompt(rng, cfg, 5) for _ in range(2)]
    want = [_oracle_generate(cfg, params, p, 14, 32) for p in prompts]

    eng = ServeEngine(cfg, params, slots=2, max_len=32, seed=0,
                      pool="paged", page_size=8, num_pages=5,
                      prefill_chunk=4, admission="incremental")
    futs = [eng.submit(_req(p, max_new=14)) for p in prompts]
    eng.run_until_idle()
    results = [f.result(0) for f in futs]
    for r, w in zip(results, want):
        assert r.tokens == w
    snap = eng.metrics.snapshot()
    assert snap["preempted"] >= 1
    assert snap["recompute_tokens"] > 0
    assert sum(r.metrics.preemptions for r in results) == snap["preempted"]
    # the kick/recompute cycle leaked nothing: every page drained
    assert eng.pool.pages_in_use == 0
    assert len(eng.pool.free_list()) == eng.pool.total_pages - 1


def test_incremental_admits_mixed_trace_eager_cannot(cfg, params):
    """Equal-memory overload: a long request (3-page full budget) plus a
    short one (2 pages) on 4 usable pages. Eager admission must reserve
    whole budgets, so it can only serialize them (max 1 concurrent slot);
    incremental reserves prompt-only pages and co-runs both (2 concurrent),
    finishing the same trace with identical greedy tokens."""
    rng = np.random.default_rng(12)
    long_p, short_p = _prompt(rng, cfg, 5), _prompt(rng, cfg, 4)
    want = [_oracle_generate(cfg, params, long_p, 14, 32),
            _oracle_generate(cfg, params, short_p, 6, 32)]

    def run(admission):
        eng = ServeEngine(cfg, params, slots=2, max_len=32, seed=0,
                          pool="paged", page_size=8, num_pages=5,
                          prefill_chunk=4, admission=admission)
        futs = [eng.submit(_req(long_p, max_new=14)),
                eng.submit(_req(short_p, max_new=6))]
        eng.run_until_idle()
        return [f.result(0).tokens for f in futs], eng.metrics.snapshot()

    eager_toks, eager = run("eager")
    incr_toks, incr = run("incremental")
    assert eager_toks == want and incr_toks == want
    # eager cannot admit both concurrently (3 + 2 pages > 4 usable)...
    assert eager["max_concurrent_slots"] == 1
    assert eager["preempted"] == 0
    # ...incremental co-runs them at the same memory
    assert incr["max_concurrent_slots"] == 2
    assert incr["pool"]["admission"] == "incremental"


def test_incremental_requires_paged_chunked(cfg, params):
    """The recompute path rides chunked prefill on the paged pool — any
    other configuration is rejected loudly at construction."""
    with pytest.raises(ValueError, match="incremental"):
        ServeEngine(cfg, params, slots=2, max_len=32, pool="dense",
                    admission="incremental")
    with pytest.raises(ValueError, match="incremental"):
        ServeEngine(cfg, params, slots=2, max_len=32, pool="paged",
                    prefill_chunk=None, admission="incremental")
    with pytest.raises(ValueError, match="admission"):
        ServeEngine(cfg, params, slots=2, max_len=32, admission="lazy")


# ---------------------------------------------------------------------------
# Checkpoint -> serving restore
# ---------------------------------------------------------------------------

class TestCheckpointRestore:
    def _train(self, cfg, tmp_path, steps=2):
        from repro.configs.base import TrainConfig
        from repro.train.trainer import Trainer
        tc = TrainConfig(total_steps=steps, warmup_steps=1,
                         checkpoint_every=1, checkpoint_dir=str(tmp_path),
                         keep_checkpoints=3)
        trainer = Trainer(cfg, tc, seq_len=16, global_batch=4)
        trainer.run(steps, resume=False)
        trainer.ckpt.wait()
        return trainer

    def test_restore_matches_live_params(self, cfg, tmp_path):
        trainer = self._train(cfg, tmp_path)
        step, restored = loader.restore_params(cfg, str(tmp_path))
        assert step == 2
        batch = {"tokens": jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)),
            jnp.int32)}
        live, _ = lm.prefill(cfg, trainer.params, batch,
                             lm.init_caches(cfg, 1, 16))
        served, _ = lm.prefill(cfg, restored, batch,
                               lm.init_caches(cfg, 1, 16))
        np.testing.assert_allclose(np.asarray(served), np.asarray(live),
                                   atol=1e-5, rtol=1e-5)
        # and the engine on restored params reproduces the live oracle
        eng = ServeEngine(cfg, restored, slots=1, max_len=32)
        prompt = np.asarray(batch["tokens"])[0]
        fut = eng.submit(_req(prompt))
        eng.run_until_idle()
        assert fut.result(0).tokens == _oracle_generate(
            cfg, trainer.params, prompt, 4, 32)

    def test_torn_checkpoint_falls_back_to_newest_valid(self, cfg,
                                                        tmp_path):
        self._train(cfg, tmp_path)
        # a torn step-3 checkpoint: data written, commit sentinel missing
        torn = os.path.join(str(tmp_path), "step_000000003")
        os.makedirs(torn)
        with open(os.path.join(torn, "manifest.json"), "w") as f:
            f.write("{}")
        # a corrupt-but-committed step 4: sentinel present, arrays garbage
        bad = os.path.join(str(tmp_path), "step_000000004")
        os.makedirs(bad)
        with open(os.path.join(bad, "manifest.json"), "w") as f:
            f.write("{}")
        with open(os.path.join(bad, "arrays.npz"), "wb") as f:
            f.write(b"not an npz")
        with open(os.path.join(bad, "_COMMITTED"), "w") as f:
            f.write("ok")
        step, restored = loader.restore_params(cfg, str(tmp_path))
        assert step == 2 and restored is not None

    def test_load_for_serving_fresh_init_fallback(self, cfg, tmp_path):
        step, params = loader.load_for_serving(cfg, str(tmp_path / "empty"))
        assert step is None and params is not None
        want = loader.init_params(cfg, seed=0)
        leaves_a = jax.tree_util.tree_leaves(params)
        leaves_b = jax.tree_util.tree_leaves(want)
        assert all(np.allclose(a, b) for a, b in zip(leaves_a, leaves_b))


# ---------------------------------------------------------------------------
# Sharded engine (8 simulated devices; CI multi-device step)
# ---------------------------------------------------------------------------

def _jitter_stage_weights(path, w):
    """Scale butterfly stage weights by a seeded 1% jitter (other leaves
    unchanged)."""
    if getattr(path[-1], "key", None) not in ("b_in", "b_out"):
        return w
    key = jax.random.fold_in(jax.random.PRNGKey(1), len(w.shape) * 7919
                             + w.size % 7919)
    return w * (1 + 0.01 * jax.random.normal(key, w.shape, w.dtype))


def _greedy_margins(cfg, params, prompt, max_new, max_len):
    """Top-2 logit gap at every step of the oracle's greedy decode."""
    caches = lm.init_caches(cfg, 1, max_len)
    logits, caches = lm.prefill(
        cfg, params, {"tokens": jnp.asarray(prompt[None, :])}, caches)
    gaps = []
    for pos in range(len(prompt), len(prompt) + max_new):
        top2 = jnp.sort(logits[0])[-2:]
        gaps.append(float(top2[1] - top2[0]))
        logits, caches = lm.decode_step(
            cfg, params, jnp.argmax(logits[0]).astype(jnp.int32)[None],
            caches, jnp.asarray(pos, jnp.int32))
    return gaps


def _sharded_prompts(cfg):
    rng = np.random.default_rng(6)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in (5, 9, 20)]


def test_fjlt_init_ties_greedy_logits_exactly():
    """Why test_sharded_engine_matches_unsharded serves jittered stage
    weights: on the fresh FJLT init, the 20-token prompt's greedy decode
    meets exact top-2 logit ties (gap 0.0) at steps 1 and 3. Step 3 is the
    token on which the sharded and unsharded engines disagreed, so argmax
    there is decided by rounding, not by the model."""
    cfg = registry.get("smollm-135m-butterfly-smoke").with_(
        compute_dtype="float32")
    params = loader.init_params(cfg, seed=0)
    gaps = _greedy_margins(cfg, params, _sharded_prompts(cfg)[2], 5, 48)
    assert gaps[1] == 0.0 and gaps[3] == 0.0, gaps


@pytest.mark.slow
def test_sharded_engine_matches_unsharded():
    """The same engine code serving under an 8-device ("data",) mesh —
    butterfly sites batch-sharded via shard_map — reproduces the
    single-device engine token-for-token, on the default PAGED pool with
    a multi-chunk prompt in the mix (page-table gather + chunked prefill
    under GSPMD).

    float32 compute: the two layouts are not bitwise equal (on XLA-CPU the
    logits differ by ~2e-5: a 1-row and a 2-row product round differently),
    so token equality is sound only where every greedy step's top-2 logit
    gap is far above that. The fresh FJLT init breaks that premise: its
    butterflies are exact ±1/√2 Hadamard stages, and with k = log2 n the
    output butterfly maps the k-dim core onto at most 2^k sign patterns, so
    whole groups of logits tie exactly (gap 0.0) and argmax is decided by
    rounding — the unsharded engine itself then changes tokens with the
    slot count. The test therefore serves weights off that lattice (the
    stage weights scaled by a seeded 1% jitter) and asserts the margin
    premise before comparing tokens.
    """
    cfg = registry.get("smollm-135m-butterfly-smoke").with_(
        compute_dtype="float32")
    params = jax.tree_util.tree_map_with_path(
        _jitter_stage_weights, loader.init_params(cfg, seed=0))
    prompts = _sharded_prompts(cfg)
    for p in prompts:
        assert min(_greedy_margins(cfg, params, p, 5, 48)) > 1e-3

    def run(context):
        eng = ServeEngine(cfg, params, slots=2, max_len=48, seed=0,
                          context=context)
        assert eng.pool.kind == "paged"
        assert prompts[2].size > eng.prefill_chunk   # multi-chunk coverage
        futs = [eng.submit(_req(p, max_new=5)) for p in prompts]
        eng.run_until_idle()
        return [f.result(0).tokens for f in futs], eng

    want, _ = run(None)
    got, eng = run(ExecutionContext(mesh_shape=(8,)))
    assert eng.ctx.mesh_layout() == "data=8"
    assert got == want


def test_engine_metrics_snapshot_races_recorder_storm():
    """EngineMetrics is mutated by the driver thread while `snapshot()`
    reads from the client thread; every recorder and both readers hold
    the metrics lock. Hammer: one thread runs the full recorder lifecycle
    in a tight loop while the main thread snapshots — every snapshot must
    be internally consistent (no torn reads, no dict-mutated-during-
    iteration), and the final state must count every request exactly
    once."""
    import threading

    from repro.serve.metrics import EngineMetrics

    m = EngineMetrics(slots=2)
    n_requests = 3000
    stop = threading.Event()
    start = threading.Barrier(2)
    storm_error = []

    def storm():
        try:
            start.wait()
            for rid in range(n_requests):
                m.on_submit(rid, prompt_len=8)
                m.on_tick()
                m.on_admit(rid)
                m.on_prefill_work(8, 0.001, chunked=True)
                m.on_prefill_done()
                m.on_first_token(rid)
                m.on_token(rid, 2)
                m.on_decode_tick(1, 1, 0.001)
                m.on_occupancy(1)
                m.on_pool_exhausted()
                m.on_finish(rid)
        except BaseException as e:     # surfaces in the main thread
            storm_error.append(e)
        finally:
            stop.set()

    t = threading.Thread(target=storm)
    t.start()
    snaps = 0
    start.wait()
    while not stop.is_set():
        snap = m.snapshot()
        # internal consistency under concurrent mutation: the finished
        # window and its percentiles come from one locked pass
        assert snap["ttft_ms"]["p50"] <= snap["ttft_ms"]["p95"]
        assert 0 <= snap["requests_finished"] <= n_requests
        snaps += 1
    t.join()
    assert not storm_error, storm_error
    final = m.snapshot()
    assert final["requests_finished"] == n_requests
    assert final["total_tokens"] == n_requests * 3
    assert final["max_concurrent_slots"] == 1
    assert snaps > 0
