"""Unified observability layer: tracer, metrics registry, validator.

Three pillars under test:

* :mod:`repro.obs.tracing` — the bounded-ring span tracer, its Chrome
  trace-event export, and the :data:`NULL_TRACER` no-op default;
* :mod:`repro.obs.registry` — the one lock-protected metrics registry
  the engine / router / pool / faults / compile cache all feed, its
  stable snapshot schema (golden-pinned here) and Prometheus exposition;
* :mod:`repro.obs.validate` — the structural Chrome-trace validator CI
  runs over the benchmark artifact.

The acceptance test drives a page-starved speculative engine through a
one-replica :class:`Router` and reconstructs one preempted request's
COMPLETE timeline from the exported trace: queue → admit → prefill →
spec → preempt → queue (again) → admit → prefill(recompute) → finish.
:meth:`Tracer.phase` is checked under a real ``jax.profiler`` session:
the profiler event carries the plain phase name.
"""

import glob
import json
import threading

import pytest

from repro.configs import registry as arch_registry
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       NullTracer, SNAPSHOT_SCHEMA, Tracer)
from repro.obs.tracing import NULL_TRACER, TRACK_ENGINE
from repro.obs.validate import TraceValidationError, validate_chrome_trace
from repro.serve import (FaultInjector, Request, Router, SamplingParams,
                         ServeEngine, loader)

ARCH = "smollm-135m-butterfly-smoke"


@pytest.fixture(scope="module")
def cfg():
    return arch_registry.get(ARCH)


@pytest.fixture(scope="module")
def params(cfg):
    return loader.init_params(cfg, seed=0)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_ring_bound_and_drop_counter():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 6
    assert tr.emitted == 10
    # oldest evicted first: the ring keeps the 4 newest
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0 and tr.emitted == 0


def test_tracer_span_and_complete_events():
    tr = Tracer()
    with tr.span("work", pid=2, tid=5, tick=7):
        pass
    t0 = tr.now()
    tr.complete("manual", t0, t0 + 1.5, pid=1, tid=0, foo="bar")
    evs = tr.events()
    assert [e["name"] for e in evs] == ["work", "manual"]
    span = evs[0]
    assert span["ph"] == "X" and span["dur"] >= 0
    assert (span["pid"], span["tid"]) == (2, 5)
    assert span["args"] == {"tick": 7}
    assert evs[1]["dur"] == 1.5
    # negative durations clamp rather than poisoning the trace
    tr.complete("backwards", 10.0, 5.0)
    assert tr.events()[-1]["dur"] == 0.0


def test_tracer_chrome_export_metadata_and_validates():
    tr = Tracer()
    tr.name_process(0, "replica 0")
    tr.name_track(0, TRACK_ENGINE, "engine")
    tr.name_track(0, 3, "req 2")
    with tr.span("outer", pid=0, tid=3):
        with tr.span("inner", pid=0, tid=3):
            pass
    doc = tr.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    names = {(e["name"], e["pid"], e["tid"]) for e in meta}
    assert ("process_name", 0, 0) in names
    assert ("thread_name", 0, 3) in names
    # validator accepts the export and strips the metadata
    evs = validate_chrome_trace(doc)
    assert {e["name"] for e in evs} == {"outer", "inner"}
    # round-trips through JSON unchanged
    validate_chrome_trace(json.loads(json.dumps(doc)))


def test_null_tracer_is_inert():
    nt = NullTracer()
    nt.instant("x")
    nt.complete("y", 0.0, 1.0)
    with nt.span("z"):
        pass
    nt.name_process(0, "p")
    nt.name_track(0, 0, "t")
    assert len(nt) == 0 and nt.emitted == 0 and nt.now() == 0.0
    assert not nt.enabled and not NULL_TRACER.enabled
    assert nt.chrome_trace()["traceEvents"] == []
    # the same span object is reused — no per-call allocation
    assert nt.span("a") is nt.span("b")


def test_engine_defaults_to_null_tracer(cfg, params):
    eng = ServeEngine(cfg, params, slots=1, max_len=32, seed=0)
    assert eng.tracer is NULL_TRACER
    assert isinstance(eng.obs, MetricsRegistry)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_identity_and_values():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    assert reg.counter("reqs_total") is c
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    v = h.value
    assert v["count"] == 3 and v["buckets"]["+Inf"] == 3
    assert v["buckets"][repr(0.1)] == 1 and v["buckets"][repr(1.0)] == 2


def test_registry_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError, match="already registered as counter"):
        reg.gauge("m")
    with pytest.raises(ValueError, match="primitive-backed"):
        reg.register_callback("m", lambda: 1, mtype="counter")
    reg.register_callback("cb", lambda: 1)
    with pytest.raises(ValueError, match="callback-backed"):
        reg.gauge("cb")
    # newest wins on callback re-register (engine rebuilds do this)
    reg.register_callback("cb", lambda: 42)
    sample = reg.snapshot()["metrics"]["cb"]["samples"][0]
    assert sample["value"] == 42


def test_registry_labels_and_exposition():
    reg = MetricsRegistry()
    reg.counter("hits_total", "hit count", labels={"replica": 0}).inc(7)
    reg.counter("hits_total", labels={"replica": 1}).inc(9)
    reg.histogram("tick_seconds", "per-tick wall",
                  buckets=(0.5,)).observe(0.25)
    text = reg.exposition()
    assert "# HELP hits_total hit count" in text
    assert "# TYPE hits_total counter" in text
    assert 'hits_total{replica="0"} 7' in text
    assert 'hits_total{replica="1"} 9' in text
    assert 'tick_seconds_bucket{le="0.5"} 1' in text
    assert 'tick_seconds_bucket{le="+Inf"} 1' in text
    assert "tick_seconds_sum 0.25" in text
    assert "tick_seconds_count 1" in text
    snap = reg.snapshot()
    assert snap["schema"] == SNAPSHOT_SCHEMA
    samples = snap["metrics"]["hits_total"]["samples"]
    assert [s["labels"] for s in samples] == [{"replica": "0"},
                                              {"replica": "1"}]
    # stable JSON round-trip
    assert json.loads(reg.snapshot_json()) == snap


def test_registry_hammer_concurrent_with_exposition():
    """PR-9-style storm, now against the shared registry: four threads
    mutate primitives (and one callback reads a racing plain int) while
    the main thread renders exposition + snapshot. Every render must be
    internally consistent and the final counts exact."""
    reg = MetricsRegistry()
    c = reg.counter("storm_total")
    g = reg.gauge("storm_depth")
    h = reg.histogram("storm_seconds", buckets=(0.5,))
    state = {"n": 0}
    reg.register_callback("storm_cb", lambda: state["n"])
    n_threads, n_iter = 4, 2000
    start = threading.Barrier(n_threads + 1)
    errors = []

    def storm():
        try:
            start.wait()
            for i in range(n_iter):
                c.inc()
                g.inc()
                g.dec()
                h.observe(0.25 if i % 2 else 0.75)
                state["n"] += 1
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=storm) for _ in range(n_threads)]
    for t in threads:
        t.start()
    start.wait()
    renders = 0
    while any(t.is_alive() for t in threads):
        snap = reg.snapshot()["metrics"]
        hv = snap["storm_seconds"]["samples"][0]["value"]
        assert hv["buckets"]["+Inf"] == hv["count"]
        assert 0 <= snap["storm_total"]["samples"][0]["value"] \
            <= n_threads * n_iter
        assert "storm_total" in reg.exposition()
        renders += 1
    for t in threads:
        t.join()
    assert not errors, errors
    total = n_threads * n_iter
    assert c.value == total
    assert g.value == 0
    assert h.value["count"] == total
    assert renders > 0


# ---------------------------------------------------------------------------
# Validator
# ---------------------------------------------------------------------------

def test_validator_rejects_malformed_events():
    ok = [{"name": "a", "ph": "X", "ts": 0.0, "dur": 2.0, "pid": 0,
           "tid": 0, "args": {}},
          {"name": "b", "ph": "X", "ts": 0.5, "dur": 1.0, "pid": 0,
           "tid": 0}]
    assert len(validate_chrome_trace(ok)) == 2
    with pytest.raises(TraceValidationError, match="missing required"):
        validate_chrome_trace([{"name": "a", "ph": "i", "pid": 0,
                                "tid": 0}])
    with pytest.raises(TraceValidationError, match="unknown phase"):
        validate_chrome_trace([{"name": "a", "ph": "Q", "ts": 0,
                                "pid": 0, "tid": 0}])
    with pytest.raises(TraceValidationError, match="without dur"):
        validate_chrome_trace([{"name": "a", "ph": "X", "ts": 0,
                                "pid": 0, "tid": 0}])
    with pytest.raises(TraceValidationError, match="traceEvents"):
        validate_chrome_trace({"events": []})


def test_validator_rejects_partial_overlap():
    bad = [{"name": "a", "ph": "X", "ts": 0.0, "dur": 2.0, "pid": 0,
            "tid": 0},
           {"name": "b", "ph": "X", "ts": 1.0, "dur": 2.0, "pid": 0,
            "tid": 0}]
    with pytest.raises(TraceValidationError, match="partially overlaps"):
        validate_chrome_trace(bad)
    # same shapes on DIFFERENT tracks are fine
    bad[1]["tid"] = 1
    validate_chrome_trace(bad)


# ---------------------------------------------------------------------------
# Golden schema: one registry for engine + pool + faults + compile + router
# ---------------------------------------------------------------------------

# The unified snapshot's metric families. A rename/removal here is a
# telemetry schema break for every downstream consumer — change this
# list deliberately, in lockstep with README's observability table.
GOLDEN_FAMILIES = [
    "router_drains_total",
    "router_max_concurrent_slots",
    "router_passes_total",
    "router_replicas",
    "router_replicas_live",
    "router_requeued_total",
    "router_shed_total",
    "router_swaps_total",
    "serve_cancelled_total",
    "serve_chunk_ticks_total",
    "serve_compile_traces_total",
    "serve_compiles_total",
    "serve_deadline_expired_total",
    "serve_decode_steps_total",
    "serve_decode_time_seconds_total",
    "serve_decode_tokens_total",
    "serve_fault_calls_total",
    "serve_fault_fired_total",
    "serve_finished_tokens_total",
    "serve_max_concurrent_slots",
    "serve_occupied_slots",
    "serve_pages_hwm",
    "serve_pages_in_use",
    "serve_pages_total",
    "serve_pool_exhausted_total",
    "serve_preempted_total",
    "serve_prefill_time_seconds_total",
    "serve_prefill_tokens_total",
    "serve_prefills_total",
    "serve_queue_depth",
    "serve_recompute_tokens_total",
    "serve_rejected_queue_full_total",
    "serve_requests_finished_total",
    "serve_slots",
    "serve_spec_accepted_draft_tokens_total",
    "serve_spec_draft_tokens_total",
    "serve_spec_k",
    "serve_spec_ticks_total",
    "serve_tick_seconds",
    "serve_ticks_total",
    "serve_trace_dropped_total",
    "serve_trace_events",
]


def test_golden_snapshot_schema(cfg, params):
    reg = MetricsRegistry()
    eng = ServeEngine(
        cfg, params, slots=2, max_len=32, pool="paged", page_size=8,
        num_pages=5, prefill_chunk=4, admission="incremental", spec_k=2,
        faults=FaultInjector(seed=3, rates={"pool.alloc": 0.0}),
        sampling=SamplingParams(), registry=reg, replica=0, seed=0)
    router = Router([eng])
    assert reg.names() == GOLDEN_FAMILIES
    snap = router.telemetry()
    assert snap["schema"] == "repro.serve/telemetry-1"
    assert set(snap) == {"schema", "summary", "metrics"}
    assert snap["metrics"]["schema"] == SNAPSHOT_SCHEMA
    assert sorted(snap["metrics"]["metrics"]) == GOLDEN_FAMILIES
    for name, fam in snap["metrics"]["metrics"].items():
        assert fam["type"] in ("counter", "gauge", "histogram"), name
        assert fam["samples"], f"{name} has no samples"
    # per-site fault families carry the site label
    sites = {s["labels"]["site"] for s in
             snap["metrics"]["metrics"]["serve_fault_calls_total"]["samples"]}
    assert sites == {"pool.alloc", "engine.tick"}
    # the doc is pure JSON
    json.dumps(snap)


# ---------------------------------------------------------------------------
# Acceptance: preempted-request timeline through the router
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def preempt_trace(cfg, params):
    """Page-starved speculative run through a one-replica Router; returns
    (tracer, registry, engine, results)."""
    reg = MetricsRegistry()
    tr = Tracer()
    eng = ServeEngine(
        cfg, params, slots=2, max_len=32, pool="paged", page_size=8,
        num_pages=5, prefill_chunk=4, admission="incremental", spec_k=2,
        sampling=SamplingParams(), tracer=tr, registry=reg, replica=0,
        seed=0)
    router = Router([eng], tracer=tr, registry=reg)
    with router:
        futs = [router.submit(Request(prompt=list(range(1, 6)),
                                      max_new_tokens=14))
                for _ in range(2)]
        results = [f.result(timeout=300) for f in futs]
    return tr, reg, eng, results


def test_preempted_request_timeline_reconstructs(preempt_trace):
    tr, reg, eng, results = preempt_trace
    assert eng.metrics.preempted >= 1, "geometry must force a preemption"
    assert eng.metrics.draft_tokens > 0, "speculation must have run"
    doc = tr.chrome_trace()
    events = validate_chrome_trace(doc)

    # find the preempted request's lane
    pre = [e for e in events if e["name"] == "preempt"]
    assert pre, "no preempt event in trace"
    lane = [e for e in events
            if e["tid"] == pre[0]["tid"] and e["pid"] == pre[0]["pid"]]
    lane.sort(key=lambda e: (e["ts"], -e.get("dur", 0.0)))
    names = [e["name"] for e in lane]
    rid = pre[0]["args"]["rid"]
    assert all(e["args"]["rid"] == rid for e in lane)

    # complete lifecycle, in order: first admission ...
    i_queue, i_admit = names.index("queue"), names.index("admit")
    i_pre = names.index("preempt")
    assert i_queue < i_admit < i_pre
    assert "prefill" in names[:i_pre]
    # ... preempted mid-flight, then REQUEUED: a second queue span whose
    # admission recomputes the lost prefix ...
    tail = names[i_pre + 1:]
    assert "queue" in tail and "admit" in tail
    j = i_pre + 1 + tail.index("queue")
    assert lane[j]["args"]["resume"] is True
    recompute = [e for e in lane[i_pre + 1:] if e["name"] == "prefill"]
    assert recompute and all(e["args"]["recompute"] for e in recompute)
    # ... and runs to completion
    assert names[-1] == "finish"
    assert lane[-1]["args"]["new_tokens"] == 14

    # speculative spans live on the engine lane
    engine_lane = {e["name"] for e in events if e["tid"] == TRACK_ENGINE}
    assert {"tick", "spec_draft", "spec_verify", "grow_pages",
            "serve.compile", "serve.decode"} <= engine_lane

    # lanes are labelled for Perfetto
    meta = {(e["name"], e.get("args", {}).get("name"))
            for e in doc["traceEvents"] if e["ph"] == "M"}
    assert ("thread_name", f"req {rid}") in meta
    assert ("thread_name", "engine") in meta

    # both requests produced identical tokens (tracing never perturbs)
    assert results[0].tokens == results[1].tokens


def test_compile_cache_emits_structured_events(preempt_trace):
    tr, reg, eng, _ = preempt_trace
    events = eng.compile_cache.events
    assert len(events) == eng.compile_cache.compiles > 0
    for ev in events:
        assert set(ev) == {"key", "seconds"}
        assert isinstance(ev["key"], str) and ev["seconds"] >= 0
    spans = [e for e in tr.events() if e["name"] == "serve.compile"]
    assert len(spans) == len(events)
    assert all(s["args"]["key"] == ev["key"]
               for s, ev in zip(spans, events))
    snap = reg.snapshot()["metrics"]
    got = snap["serve_compiles_total"]["samples"][0]["value"]
    assert got == eng.compile_cache.compiles


def test_reset_metrics_rebases_pool_hwm_and_clears_trace(preempt_trace):
    """Regression: reset_metrics() used to re-import the pool's surviving
    high-water mark through sync_pool, so `pages_hwm` (and the tracer
    ring) survived a reset. After a drained run + reset, the pool stats
    must rebase to current occupancy and the ring must be empty."""
    tr, reg, eng, _ = preempt_trace
    before = eng.metrics.snapshot()
    assert before["pool"]["pages_hwm"] > 0
    assert len(tr) > 0
    eng.reset_metrics()
    after = eng.metrics.snapshot()
    assert after["pool"]["pages_hwm"] == after["pool"]["pages_in_use"] == 0
    assert after["preempted"] == 0 and after["requests_finished"] == 0
    assert len(tr) == 0 and tr.dropped == 0
    # registry callbacks read through the engine: post-reset they report
    # the fresh EngineMetrics, not the old object
    snap = reg.snapshot()["metrics"]
    assert snap["serve_preempted_total"]["samples"][0]["value"] == 0
    assert snap["serve_pages_hwm"]["samples"][0]["value"] == 0
    # track names were re-registered after clear() wiped them
    meta = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)


# ---------------------------------------------------------------------------
# Phases: profiler annotations on the device trace's clock
# ---------------------------------------------------------------------------

def test_null_tracer_phase_only_annotates():
    import jax.numpy as jnp
    import numpy as np

    x = jnp.arange(6.0)
    with NULL_TRACER.phase("serve.decode", pid=0, tick=3) as ann:
        y = np.asarray(x * 2)
    np.testing.assert_array_equal(y, np.arange(6.0) * 2)
    assert type(ann).__name__ == "TraceAnnotation"
    assert len(NULL_TRACER) == 0 and NULL_TRACER.emitted == 0


def test_tracer_phase_records_span_and_plain_profiler_event(tmp_path):
    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.phase("serve.decode", pid=1, tick=7):
            with tr.phase("serve.compile", pid=1, key="k"):
                pass
        with NULL_TRACER.phase("train.readback", step=2):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = tr.events()
    assert [e["name"] for e in spans] == ["serve.compile", "serve.decode"]
    assert spans[1]["args"] == {"tick": 7} and spans[1]["pid"] == 1
    validate_chrome_trace(tr.chrome_trace())
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for p in ProfileData.from_file(path).planes
             for line in p.lines for ev in line.events}
    # the args stay in the Chrome span: the profiler event is the plain
    # phase name, traced or not
    assert {"serve.decode", "serve.compile", "train.readback"} <= names
    assert not any(n.startswith("serve.decode#") for n in names)


def test_one_prefill_and_decode_span_per_request(cfg, params):
    """A request's lane holds one ``prefill`` span (over all its chunks)
    and one ``decode`` span (first token to finish), whatever the number
    of ticks; the engine lane holds the tick's phases."""
    tr = Tracer()
    eng = ServeEngine(cfg, params, slots=2, max_len=64, prefill_chunk=4,
                      seed=0, tracer=tr)
    futs = [eng.submit(Request(prompt=list(range(1, n)), max_new_tokens=6))
            for n in (11, 4, 7)]
    eng.run_until_idle()
    assert all(len(f.result(0).tokens) == 6 for f in futs)
    events = validate_chrome_trace(tr.chrome_trace())
    for rid in range(3):
        lane = [e for e in events if e["tid"] == rid + 1 and e["ph"] == "X"]
        assert [e["name"] for e in lane] == ["queue", "prefill", "decode"]
        prefill, decode = lane[1], lane[2]
        assert prefill["ts"] + prefill["dur"] <= decode["ts"] + 1e-3
        assert decode["args"]["new_tokens"] == 6
    engine = {e["name"] for e in events if e["tid"] == TRACK_ENGINE}
    assert {"serve.admit", "serve.chunk", "serve.first_token",
            "serve.decode", "serve.readback", "serve.emit"} <= engine
    assert not any(e["name"] == "decode" for e in events
                   if e["tid"] == TRACK_ENGINE)
