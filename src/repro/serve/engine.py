"""Continuous-batching inference engine for the butterfly LMs.

The engine owns a fixed pool of ``slots`` decode lanes over ONE pooled
cache tree — a :class:`repro.serve.cache.CachePool`, paged by default
(``pool="paged"``, dense rows via ``pool="dense"`` for bisection; archs
with sequential-state blocks fall back to dense automatically) — and runs
a strict tick loop:

  1. **Admit** — while a slot is free and requests are queued, pop one and
     reserve pages for it. Under ``admission="eager"`` (the PR-6 policy,
     kept for bisection) the reservation is the request's *full* token
     budget (``alloc_pages(slot, n_front + prompt + max_new)``) —
     deadlock-free with no preemption path. Under
     ``admission="incremental"`` (vLLM's actual policy) only the prompt's
     pages are reserved; the decode budget is allocated page-by-page as
     the slot actually decodes (step 2b), so slots whose requests *could
     not all co-reside at full budget* still run concurrently. Either
     way a pool that cannot cover the reservation raises
     :class:`~repro.serve.cache.PoolExhausted` and the engine leaves the
     request queued, retrying after pages free — exhaustion is
     backpressure, never a crash.
  2b. **Grow / preempt** (incremental admission only) — before the compute
     ticks, every live slot's page table is grown to cover this tick's
     writes (the next decode position; ``+1`` for a prompt whose final
     chunk lands this tick), oldest slot first. When the pool exhausts
     mid-growth the engine *preempts its youngest slot*: frees its pages,
     re-queues the request at the queue head with its already-generated
     tokens appended to the prompt, and recomputes the whole prefix via
     the ordinary chunked-prefill path on re-admission. Greedy decoding
     is deterministic, so the resumed request's output is token-identical
     to the never-preempted run (CI-gated in ``tests/test_serve.py``).
  2. **Chunked prefill** (paged, full-attention archs) — admitted prompts
     are processed as fixed-size chunks (``prefill_chunk`` tokens) through
     ONE compiled pool-wide step (:func:`repro.train.steps.
     make_chunk_prefill_step`), interleaved with decode ticks, so a long
     prompt never stalls in-flight decodes and every prompt length shares
     a single compile. Archs the chunk path can't serve (vision frontend,
     encoders, sliding-window or cross-attention caches) admit through the
     PR-5 whole-bucket prefill instead, scattered into the pool via
     :meth:`CachePool.write_slot`.
  3. **Decode** — ONE fused pooled step (:func:`repro.train.steps.
     make_pool_serve_step`) advances every decoding slot by one token:
     per-slot positions, per-slot page tables (inactive lanes redirected
     to the trash page), per-slot active masks. Finished slots resolve
     their futures, free their pages for recycling, and the next tick's
     admission refills them — no stall, no re-batching barrier.
  3b. **Speculative decode** (``spec_k > 0``, greedy + paged + chunked
     prefill only) — draft-k-verify-1 replaces step 3: a near-free draft
     (the model's own output head — a fixed-structure butterfly sandwich
     on butterfly-compressed archs — over a residual-stream state
     advanced by embedding feedback; :func:`repro.train.steps.
     make_draft_step`) proposes ``spec_k`` tokens per slot, then ONE
     batched pass of the full model verifies all positions
     (:func:`repro.train.steps.make_spec_decode_step`) and each slot
     commits its accepted prefix — 1 to ``spec_k + 1`` tokens per tick.
     Rejected positions never advance ``cur_pos``, so their stale KV
     writes stay inert under the validity mask (the same invariant the
     trash page relies on), and greedy verification makes the committed
     stream token-identical to non-speculative decoding (CI-gated).

Requests are frozen :class:`Request` values — ``submit()`` takes exactly
one of them; the pre-paging positional ``submit(prompt, max_new_tokens=…)``
shape raises ``TypeError`` with the migration spelled out (repo policy
post-PR 5: renamed surfaces break loudly, no loose-kwarg shims).

Request lifecycle failures are *typed*, so callers can tell load-shedding
from bugs: :class:`QueueFull` (bounded admission queue, raised at
``submit``), :class:`DeadlineExceeded` (per-request ``deadline_ticks`` /
``deadline_s`` blown — queued or mid-decode, the slot and its pages free
immediately), :class:`RequestCancelled` (``cancel(rid)``), and the pool's
:class:`~repro.serve.cache.PoolExhausted` (internal backpressure, never
surfaced to a future). A :class:`repro.serve.faults.FaultInjector` passed
as ``faults=`` forces these paths on a seeded schedule.

Compilation is explicit: every jitted function lives in a
:class:`CompileCache` keyed on ``(kind, arch, shape/bucket, pool kind,
sampling, ExecutionContext)``, with a trace counter the tests gate on —
chunked admission traces ONE prefill for every prompt length; bucketed
admission traces once per bucket.

The engine is ExecutionContext-native: it resolves ONE context at
construction (explicit ``context=`` > ambient > the arch's
``ButterflyConfig``), traces everything inside ``use_execution`` (plus
``use_sharding`` when the context carries a mesh), so the same engine
serves on one CPU or batch-shards its butterfly sites across an 8-device
simulated mesh via :mod:`repro.runtime.butterfly_sharding`.

Threading model: ``submit()`` is thread-safe; ``step()`` /
``run_until_idle()`` must be driven from one thread (the
:class:`repro.serve.client.ServeClient` wraps exactly that driver thread
and hands out futures).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import context as exctx
from repro.obs.registry import MetricsRegistry
from repro.obs.tracing import NULL_TRACER, TRACK_ENGINE
from repro.runtime import sharding as rsh
from repro.serve import cache as cache_lib
from repro.serve import sampling as sampling_lib
from repro.serve.cache import PoolExhausted
from repro.serve.metrics import EngineMetrics
from repro.train import steps as steps_lib

# Block types whose caches mix positions sequentially (recurrent state) or
# ring-buffer by position: right-padded bucket prefill would fold the pads
# into the state, so these archs prefill at exact prompt lengths instead
# (one compile per distinct length — the trade the engine makes explicit).
SEQUENTIAL_STATE_BLOCKS = ("rec", "mlstm", "slstm", "local")


def _fmt_compile_key(key: Tuple) -> str:
    """Human/JSON-safe rendering of a compile key — ExecutionContext
    members render through their one-line ``describe()``."""
    return " | ".join(
        k.describe() if hasattr(k, "describe") else str(k) for k in key)


class CompileCache:
    """Explicit jit cache with a trace counter and structured events.

    ``get(key, build)`` memoizes the *compiled callable* per key;
    :meth:`counted_jit` wraps the pre-jit function so every retrace bumps
    ``traces[key]`` (the function body only executes while jax traces —
    cached executions never touch it). The serving tests gate on exactly
    this counter: one trace per (shape, context), ever. The wrapper is
    named after the key's kind (``serve_decode``, ``serve_chunk_prefill``
    …), so the device program is ``jit_serve_<kind>`` in a profile.

    Cold compiles are structured events: the first call through a key is
    timed, run inside the ``serve.compile`` phase (a Chrome span on the
    tracer's engine lane whose args carry the formatted key) and appended
    to ``events``, so compile storms are visible per-replica in the
    Chrome trace. The timing wrapper replaces itself after the first
    call, so warm calls pay nothing.
    """

    def __init__(self, tracer=None, pid: int = 0):
        self._fns: Dict[Tuple, Callable] = {}
        self.traces: Dict[Tuple, int] = {}
        self.events: List[Dict] = []
        self._tracer = tracer if tracer is not None else NULL_TRACER
        self._pid = int(pid)

    def get(self, key: Tuple, build: Callable[[], Callable]) -> Callable:
        fn = self._fns.get(key)
        if fn is None:
            inner = build()

            def cold(*args, __key=key, __inner=inner, **kwargs):
                name = _fmt_compile_key(__key)
                t0 = time.monotonic()
                with self._tracer.phase("serve.compile", pid=self._pid,
                                        key=name):
                    out = __inner(*args, **kwargs)
                self._fns[__key] = __inner   # warm path: straight through
                self.events.append(
                    {"key": name,
                     "seconds": round(time.monotonic() - t0, 6)})
                return out

            fn = self._fns[key] = cold
        return fn

    def counted_jit(self, key: Tuple, fn: Callable, **jit_kw) -> Callable:
        def traced(*args, **kwargs):
            self.traces[key] = self.traces.get(key, 0) + 1
            return fn(*args, **kwargs)
        traced.__name__ = traced.__qualname__ = f"serve_{key[0]}"
        return jax.jit(traced, **jit_kw)

    @property
    def compiles(self) -> int:
        return len(self._fns)

    def keys(self) -> List[Tuple]:
        return list(self._fns)


class QueueFull(RuntimeError):
    """The bounded admission queue shed this submit (``queue_limit``
    queued requests already waiting). Typed so a client can distinguish
    load-shedding (retry later, against another replica) from a bug."""

    def __init__(self, limit: int):
        super().__init__(
            f"admission queue full ({limit} requests waiting); retry "
            f"later or raise queue_limit")
        self.limit = limit


class DeadlineExceeded(RuntimeError):
    """The request blew its ``deadline_ticks``/``deadline_s`` budget —
    queued or mid-decode — and was dropped, its slot and pages freed."""

    def __init__(self, rid: int, reason: str):
        super().__init__(f"request {rid} deadline exceeded: {reason}")
        self.rid = rid


class RequestCancelled(RuntimeError):
    """The request was cancelled via ``cancel(rid)`` before finishing."""

    def __init__(self, rid: int):
        super().__init__(f"request {rid} cancelled")
        self.rid = rid


_SUBMIT_MIGRATION = (
    "takes a single repro.serve.Request — the positional "
    "submit(prompt, max_new_tokens=..., stop_token=..., extras=...) form "
    "was removed. Migrate:\n"
    "    submit(Request(prompt=prompt, max_new_tokens=16,\n"
    "                   stop_token=None, extras=None))")


@dataclass(frozen=True, eq=False)
class Request:
    """One generation request — the frozen value ``submit()`` takes.

    ``prompt`` is normalized to a tuple of ints at construction (any int
    sequence/array is accepted). ``sampling=None`` means the engine-wide
    policy; a non-None value must equal it — the pooled decode step bakes
    sampling in at trace time, so heterogeneous per-request sampling is
    rejected loudly rather than silently ignored. ``rid=None`` lets the
    engine assign its sequence number; an explicit rid must be unique
    among live requests.

    Deadlines are measured from submission: ``deadline_ticks`` in the
    deterministic engine-tick clock (what tests assert against),
    ``deadline_s`` in wall seconds (what an operator's SLO means). A
    request past either resolves its future with
    :class:`DeadlineExceeded`, freeing its slot and pages — a stuck or
    abandoned caller can no longer hold capacity forever.
    """

    prompt: Tuple[int, ...]
    max_new_tokens: int = 16
    sampling: Optional[sampling_lib.SamplingParams] = None
    stop_token: Optional[int] = None
    extras: Optional[Mapping] = None       # frontend_embeds / frames
    rid: Optional[int] = None
    deadline_ticks: Optional[int] = None   # engine ticks after submit
    deadline_s: Optional[float] = None     # wall seconds after submit

    def __post_init__(self):
        prompt = tuple(int(t) for t in
                       np.asarray(self.prompt, np.int32).reshape(-1))
        object.__setattr__(self, "prompt", prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{self.max_new_tokens}")
        for name in ("deadline_ticks", "deadline_s"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"{name} must be positive, got {v}")


@dataclass
class GenerationResult:
    """What a request's future resolves to."""

    rid: int
    prompt: np.ndarray
    tokens: List[int]                      # all generated tokens, in order
    metrics: object                        # RequestMetrics


@dataclass
class _Slot:
    """Host-side state of one occupied decode lane (or queued request).

    ``prompt`` is the *original* prompt the result reports;
    ``prefill_seq`` is what the next admission actually prefills — equal
    to ``prompt`` on first admission, ``prompt + tokens generated so
    far`` after a preemption (the recompute path). ``tokens`` survives
    preemption, so the resumed run appends where the kicked run stopped.
    """

    req: Request
    rid: int
    future: Future
    prompt: np.ndarray
    prefill_seq: np.ndarray = None         # defaults to prompt (submit)
    tokens: List[int] = field(default_factory=list)
    cur_pos: int = 0                       # absolute cache write position
    last_token: int = -1
    prefilled: int = -1                    # prefill_seq tokens chunk-
    #                                        prefilled so far; -1 = not in
    #                                        chunk phase
    admit_seq: int = -1                    # admission order; youngest =
    #                                        highest = preemption victim
    anchor: Optional[np.ndarray] = None    # (E,) pre-final-norm backbone
    #                                        state at the last committed
    #                                        input position — the draft
    #                                        state seed (spec_k > 0)
    trace_t0: float = 0.0                  # tracer timestamp of the last
    #                                        queue entry (submit / preempt
    #                                        requeue / adopt) — the start
    #                                        of the next "queue" span
    lane: Optional[tuple] = None           # the request lane's open
    #                                        "prefill" / "decode" span:
    #                                        (name, tracer start, args)

    def __post_init__(self):
        if self.prefill_seq is None:
            self.prefill_seq = self.prompt

    @property
    def prefilling(self) -> bool:
        return 0 <= self.prefilled < self.prefill_seq.size

    @property
    def decoding(self) -> bool:
        return not self.prefilling


class ServeEngine:
    """Continuous-batching engine over a fixed decode-slot pool.

    * ``slots`` — decode lanes (the pooled batch size of the serve step).
    * ``max_len`` — per-slot token budget: every request must satisfy
      ``prompt_len + max_new_tokens <= max_len``.
    * ``pool`` — cache pool kind: ``"paged"`` (default; falls back to
      dense for sequential-state archs) or ``"dense"`` (the PR-5 layout,
      kept for bisection). See :mod:`repro.serve.cache`.
    * ``page_size`` / ``num_pages`` — paged-pool geometry; ``num_pages``
      defaults to dense-equivalent capacity plus the trash page.
    * ``prefill_chunk`` — chunked-prefill chunk size (paged, full-attention
      archs only; ``None``/0 disables chunking and admits through the
      whole-bucket path even on a paged pool).
    * ``sampling`` — engine-wide :class:`SamplingParams` (a trace-time
      constant of the serve step; greedy by default).
    * ``admission`` — page reservation policy: ``"eager"`` (default; the
      PR-6 whole-budget reservation, deadlock-free, no preemption) or
      ``"incremental"`` (prompt-only reservation + per-tick decode growth
      + preempt-youngest/recompute on exhaustion — vLLM's policy; needs
      the paged pool with chunked prefill, since recompute rides the
      chunked-prefill path).
    * ``spec_k`` — speculative decoding: number of draft tokens proposed
      per slot per tick (0 = off). Each decode tick drafts ``spec_k``
      tokens through the model's own output head (butterfly on
      butterfly-compressed archs), verifies all of them in ONE batched
      full-model pass, and commits the accepted prefix — 1 to
      ``spec_k + 1`` tokens per slot per tick. Requires greedy sampling
      (exactly lossless — acceptance only affects speed) and the paged
      pool with chunked prefill (the verify pass and the draft anchor
      ride that machinery).
    * ``queue_limit`` — bounded admission queue: a submit arriving while
      ``queue_limit`` requests already wait raises :class:`QueueFull`
      instead of growing the queue unboundedly. ``None`` = unbounded.
    * ``faults`` — optional :class:`repro.serve.faults.FaultInjector`;
      threaded into the page pool (``pool.alloc``) and the tick loop
      (``engine.tick``) so tests drive every recovery path on a seeded,
      reproducible schedule.
    * ``context`` — execution policy; resolved once here, exactly like the
      ``Trainer`` (explicit > ambient > ``cfg.butterfly`` > env/platform).
    * ``tracer`` — a :class:`repro.obs.Tracer` recording the span
      timeline (per-request lanes ``tid = rid + 1``, engine lane
      ``tid = 0``, process row ``pid = replica``). Default: the no-op
      :data:`~repro.obs.NULL_TRACER` — tracing off costs nothing
      measurable (gated by the ``serve/trace_e2e`` bench row). Either
      way the tick's phases (``serve.admit`` … ``serve.emit``) annotate
      a ``jax.profiler`` session, about a microsecond each without one.
    * ``registry`` — a :class:`repro.obs.MetricsRegistry` this engine
      registers its collectors into (callbacks reading the live
      counters, labelled ``{"replica": str(replica)}``); pass one shared
      registry across replicas for a single exposition surface. Default:
      a private registry (``engine.obs``).
    * ``replica`` — replica id: the trace ``pid`` and the ``replica``
      metric label.
    * ``scrub_freed_slots`` — re-init a slot's cache state when its request
      finishes; off by default since admission overwrites it anyway.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 128, pool: str = "paged",
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = 16,
                 sampling: sampling_lib.SamplingParams = sampling_lib.GREEDY,
                 admission: str = "eager", spec_k: int = 0,
                 queue_limit: Optional[int] = None,
                 faults=None,
                 context: exctx.ContextLike = None, seed: int = 0,
                 min_bucket: int = 8, scrub_freed_slots: bool = False,
                 tracer=None, registry: Optional[MetricsRegistry] = None,
                 replica: int = 0):
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        if admission not in ("eager", "incremental"):
            raise ValueError(f"unknown admission policy {admission!r}: "
                             f"expected 'eager' or 'incremental'")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1 or None, got "
                             f"{queue_limit}")
        self.cfg = cfg
        self.slots = slots
        self.max_len = int(max_len)
        self.sampling = sampling
        self.min_bucket = int(min_bucket)
        self.scrub_freed_slots = scrub_freed_slots
        self.ctx = exctx.resolve_execution(
            context,
            default=exctx.ExecutionContext.from_butterfly_config(
                cfg.butterfly))
        self.mesh = self.ctx.mesh
        self._params = params
        self._n_front = (cfg.frontend_tokens if cfg.frontend == "vision"
                         else 0)
        types = set(cfg.block_unit) | set(cfg.tail_layers)
        self._exact_buckets = bool(types & set(SEQUENTIAL_STATE_BLOCKS))
        self.pool = cache_lib.make_pool(cfg, slots, self.max_len,
                                        kind=pool, page_size=page_size,
                                        num_pages=num_pages)
        self.prefill_chunk = (
            int(prefill_chunk)
            if (prefill_chunk and self.pool.kind == "paged"
                and cache_lib.chunked_prefill_supported(cfg)) else None)
        self.admission = admission
        if admission == "incremental" and (
                self.pool.kind != "paged" or self.prefill_chunk is None):
            raise ValueError(
                "admission='incremental' needs the paged pool with chunked "
                "prefill (preempted requests recompute through the chunk "
                f"path); this engine resolved pool={self.pool.kind!r}, "
                f"prefill_chunk={self.prefill_chunk!r} — use "
                "admission='eager' for this arch/pool")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = int(spec_k)
        if self.spec_k:
            if not sampling.greedy:
                raise ValueError(
                    "spec_k > 0 requires greedy sampling (temperature=0): "
                    "speculative verification commits the model's argmax "
                    "targets, which is only lossless under greedy — got "
                    f"{sampling}")
            if self.pool.kind != "paged" or self.prefill_chunk is None:
                raise ValueError(
                    "spec_k > 0 needs the paged pool with chunked prefill "
                    "(the multi-position verify pass and the draft anchor "
                    "ride the chunk machinery); this engine resolved "
                    f"pool={self.pool.kind!r}, "
                    f"prefill_chunk={self.prefill_chunk!r} — use spec_k=0 "
                    "for this arch/pool")
        self.queue_limit = queue_limit
        self.faults = faults
        self.pool.faults = faults
        self._caches = self.pool.init()
        self._slots: List[Optional[_Slot]] = [None] * slots
        self._queue: collections.deque = collections.deque()
        self._lock = threading.Lock()
        self._next_rid = 0
        self._admit_seq = 0
        self._cancels: set = set()
        self._key = jax.random.PRNGKey(seed)
        self.replica = int(replica)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.obs = registry if registry is not None else MetricsRegistry()
        self._name_tracks()
        self.compile_cache = CompileCache(tracer=self.tracer,
                                          pid=self.replica)
        self.metrics = self._fresh_metrics()
        self._sample_fn = functools.partial(sampling_lib.sample_logits,
                                            params=sampling)
        self._tick_hist = self.obs.histogram(
            "serve_tick_seconds", "wall time per engine tick",
            labels={"replica": str(self.replica)})
        self._register_obs()

    def _fresh_metrics(self, history: int = 1024) -> EngineMetrics:
        return EngineMetrics(slots=self.slots, max_request_history=history,
                             pool_kind=self.pool.kind,
                             admission=self.admission,
                             total_pages=self.pool.total_pages,
                             spec_k=self.spec_k)

    # -- observability ------------------------------------------------

    def _name_tracks(self) -> None:
        self.tracer.name_process(
            self.replica, f"replica {self.replica} · {self.cfg.name}")
        self.tracer.name_track(self.replica, TRACK_ENGINE, "engine")

    def _register_obs(self) -> None:
        """Register this engine's collectors into ``self.obs``.

        Everything is a callback closing over ``self`` — NOT over the
        current ``EngineMetrics`` object — so ``reset_metrics()``'s
        object swap is transparently reflected, and recording costs the
        hot path nothing (values are read lazily at collection time).
        Re-registering under the same ``(name, labels)`` replaces the
        old callback, so rebuilding an engine against a shared registry
        (checkpoint swap, tests) never errors.
        """
        reg = self.obs
        labels = {"replica": str(self.replica)}

        def counter(name, fn, help):
            reg.register_callback(name, fn, mtype="counter", help=help,
                                  labels=labels)

        def gauge(name, fn, help):
            reg.register_callback(name, fn, mtype="gauge", help=help,
                                  labels=labels)

        counter("serve_ticks_total", lambda: self.metrics.ticks,
                "engine ticks (the deterministic clock)")
        counter("serve_requests_finished_total",
                lambda: self.metrics.requests_finished,
                "requests finished (lifetime)")
        counter("serve_finished_tokens_total",
                lambda: self.metrics.finished_tokens,
                "tokens over finished requests (lifetime)")
        counter("serve_decode_steps_total",
                lambda: self.metrics.decode_steps,
                "pooled decode tick invocations")
        counter("serve_decode_tokens_total",
                lambda: self.metrics.decode_tokens,
                "tokens emitted by pooled decode ticks")
        counter("serve_prefills_total", lambda: self.metrics.prefills,
                "prompts prefilled")
        counter("serve_prefill_tokens_total",
                lambda: self.metrics.prefill_tokens,
                "prompt tokens processed (pre-padding)")
        counter("serve_chunk_ticks_total",
                lambda: self.metrics.chunk_ticks,
                "chunked-prefill pool invocations")
        counter("serve_preempted_total", lambda: self.metrics.preempted,
                "slots kicked mid-flight for pages")
        counter("serve_recompute_tokens_total",
                lambda: self.metrics.recompute_tokens,
                "already-computed tokens re-prefilled after preemption")
        counter("serve_cancelled_total", lambda: self.metrics.cancelled,
                "requests cancelled by the client")
        counter("serve_deadline_expired_total",
                lambda: self.metrics.deadline_expired,
                "requests failed on their deadline")
        counter("serve_rejected_queue_full_total",
                lambda: self.metrics.rejected_queue_full,
                "submits shed by the bounded queue")
        counter("serve_pool_exhausted_total",
                lambda: self.metrics.pool_exhausted_events,
                "admissions/growth deferred or kicked on PoolExhausted")
        counter("serve_spec_ticks_total", lambda: self.metrics.spec_ticks,
                "speculative decode pool invocations")
        counter("serve_spec_draft_tokens_total",
                lambda: self.metrics.draft_tokens,
                "draft proposals into the verify pass")
        counter("serve_spec_accepted_draft_tokens_total",
                lambda: self.metrics.accepted_draft_tokens,
                "draft proposals that survived verification")
        counter("serve_decode_time_seconds_total",
                lambda: self.metrics.decode_time_s,
                "wall seconds inside pooled decode calls")
        counter("serve_prefill_time_seconds_total",
                lambda: self.metrics.prefill_time_s,
                "wall seconds inside prefill calls")
        counter("serve_compiles_total",
                lambda: self.compile_cache.compiles,
                "cold compiles through the CompileCache")
        counter("serve_compile_traces_total",
                lambda: sum(self.compile_cache.traces.values()),
                "jit (re)traces across all compile keys")
        counter("serve_trace_dropped_total", lambda: self.tracer.dropped,
                "trace events evicted from the bounded ring")
        gauge("serve_slots", lambda: self.slots, "decode lanes")
        gauge("serve_occupied_slots", lambda: self.occupied_slots(),
              "lanes currently holding an admitted request")
        gauge("serve_queue_depth", lambda: self.queued(),
              "requests waiting for admission")
        gauge("serve_max_concurrent_slots",
              lambda: self.metrics.max_concurrent_slots,
              "high-water mark of occupied slots")
        gauge("serve_spec_k", lambda: self.spec_k,
              "draft tokens proposed per slot tick (0 = off)")
        gauge("serve_pages_total", lambda: self.pool.total_pages,
              "physical pages incl. the trash page")
        gauge("serve_pages_in_use", lambda: self.pool.pages_in_use,
              "pages currently allocated to slots")
        gauge("serve_pages_hwm", lambda: self.pool.pages_hwm,
              "allocator high-water mark (rebased by reset_metrics)")
        gauge("serve_trace_events", lambda: len(self.tracer),
              "events currently buffered in the trace ring")
        inj = self.faults
        if inj is not None and hasattr(inj, "calls") \
                and hasattr(inj, "fired"):
            from repro.serve.faults import SITES
            for site in SITES:
                reg.register_callback(
                    "serve_fault_calls_total",
                    (lambda s=site: self.faults.calls.get(s, 0)),
                    mtype="counter",
                    help="instrumented fault-site checks",
                    labels={**labels, "site": site})
                reg.register_callback(
                    "serve_fault_fired_total",
                    (lambda s=site: self.faults.fired.get(s, 0)),
                    mtype="counter",
                    help="fault-site checks that fired",
                    labels={**labels, "site": site})

    def telemetry(self) -> Dict:
        """The unified telemetry document: the registry snapshot (ONE
        schema across engine/pool/faults/compile-cache) plus the
        human-oriented summary dict."""
        return {"schema": "repro.serve/telemetry-1",
                "summary": self.metrics.snapshot(),
                "metrics": self.obs.snapshot()}

    # -- execution scope ----------------------------------------------

    def _scope(self):
        """Ambient contexts live whenever a jitted fn may (re)trace: the
        frozen ExecutionContext, plus the sharding ctx for a mesh — the
        Trainer's exact pattern."""
        stack = contextlib.ExitStack()
        stack.enter_context(exctx.use_execution(self.ctx))
        if self.mesh is not None:
            stack.enter_context(rsh.use_sharding(self.mesh))
        return stack

    # -- compiled steps ------------------------------------------------

    def bucket_for(self, prompt_len: int) -> int:
        """Prefill bucket for a prompt: next power of two (>= min_bucket,
        <= max_len), or the exact length for sequential-state archs where
        padded prefill would corrupt the state."""
        if self._exact_buckets:
            return prompt_len
        b = self.min_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.max_len)

    def _prefill_fn(self, bucket: int) -> Callable:
        key = ("prefill", self.cfg.name, bucket, 1, self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(
                key, steps_lib.make_bucket_prefill_step(self.cfg,
                                                        self.max_len))))

    def _chunk_fn(self) -> Callable:
        key = ("chunk_prefill", self.cfg.name, self.slots,
               self.prefill_chunk, self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(
                key, steps_lib.make_chunk_prefill_step(self.cfg),
                donate_argnums=(2,))))

    def _decode_fn(self) -> Callable:
        key = ("decode", self.cfg.name, self.slots, self.pool.kind,
               self.sampling, self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(
                key,
                steps_lib.make_pool_serve_step(
                    self.cfg, self._sample_fn,
                    paged=(self.pool.kind == "paged")),
                donate_argnums=(2,))))

    def _spec_verify_fn(self) -> Callable:
        key = ("spec_verify", self.cfg.name, self.slots, self.spec_k,
               self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(
                key, steps_lib.make_spec_decode_step(self.cfg, self.spec_k),
                donate_argnums=(2,))))

    def _draft_fn(self) -> Callable:
        key = ("spec_draft", self.cfg.name, self.slots, self.spec_k,
               self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(
                key, steps_lib.make_draft_step(self.cfg, self.spec_k))))

    def _insert_fn(self) -> Callable:
        key = ("insert", self.cfg.name, self.slots, self.pool.kind,
               self.ctx)
        if self.pool.kind == "paged":
            def build():
                return self.compile_cache.counted_jit(
                    key,
                    lambda caches, sub, slot, page_row:
                        self.pool.write_slot(caches, sub, slot, page_row),
                    donate_argnums=(0,))
        else:
            def build():
                return self.compile_cache.counted_jit(
                    key,
                    lambda caches, sub, slot:
                        self.pool.write_slot(caches, sub, slot),
                    donate_argnums=(0,))
        return self.compile_cache.get(key, build)

    def _reset_fn(self) -> Callable:
        key = ("reset", self.cfg.name, self.slots, self.pool.kind,
               self.ctx)
        if self.pool.kind == "paged":
            def build():
                return self.compile_cache.counted_jit(
                    key,
                    lambda caches, slot, page_row:
                        self.pool.reset_slot(caches, slot, page_row),
                    donate_argnums=(0,))
        else:
            def build():
                return self.compile_cache.counted_jit(
                    key,
                    lambda caches, slot: self.pool.reset_slot(caches, slot),
                    donate_argnums=(0,))
        return self.compile_cache.get(key, build)

    def _first_token_fn(self) -> Callable:
        key = ("sample", self.cfg.name, self.sampling, self.ctx)
        return self.compile_cache.get(key, lambda: (
            self.compile_cache.counted_jit(key, self._sample_fn)))

    # -- client surface ------------------------------------------------

    def submit(self, request: Request, *legacy_args, **legacy_kwargs
               ) -> Future:
        """Queue a :class:`Request`; returns a future resolving to a
        :class:`GenerationResult`. Thread-safe."""
        if not isinstance(request, Request) or legacy_args or legacy_kwargs:
            raise TypeError(f"ServeEngine.submit() {_SUBMIT_MIGRATION}")
        plen = len(request.prompt)
        if plen + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt_len {plen} + max_new_tokens "
                f"{request.max_new_tokens} exceeds the engine's per-slot "
                f"budget max_len={self.max_len}")
        if (request.sampling is not None
                and request.sampling != self.sampling):
            raise ValueError(
                "per-request sampling must match the engine-wide policy "
                f"(engine: {self.sampling}, request: {request.sampling}) — "
                "sampling is a trace-time constant of the pooled decode "
                "step; run a second engine for a different policy")
        if isinstance(self.pool, cache_lib.PagedCachePool):
            need = self.pool.pages_for(
                self._n_front + plen + request.max_new_tokens)
            usable = self.pool.total_pages - 1
            if need > usable:
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{usable} usable pages — it could never be admitted "
                    f"(raise num_pages or lower the request budget)")
        with self._lock:
            if (self.queue_limit is not None
                    and len(self._queue) >= self.queue_limit):
                # bounded queue: shed load with a typed error the caller
                # can retry on, instead of queueing unboundedly
                self.metrics.on_queue_full()
                self.tracer.instant("shed", pid=self.replica,
                                    tid=TRACK_ENGINE, reason="queue_full",
                                    prompt_len=plen)
                raise QueueFull(self.queue_limit)
            if request.rid is None:
                rid = self._next_rid
            else:
                rid = int(request.rid)
                if self.metrics.request(rid) is not None:
                    raise ValueError(f"rid {rid} is already in flight")
            self._next_rid = max(self._next_rid, rid) + 1
            slot = _Slot(req=request, rid=rid, future=Future(),
                         prompt=np.asarray(request.prompt, np.int32))
            slot.trace_t0 = self.tracer.now()
            self.metrics.on_submit(rid, slot.prompt.size)
            self._queue.append(slot)
        return slot.future

    def has_work(self) -> bool:
        with self._lock:
            queued = bool(self._queue)
        return queued or any(s is not None for s in self._slots)

    def occupied_slots(self) -> int:
        """Decode lanes currently holding an admitted request."""
        return sum(s is not None for s in self._slots)

    def queued(self) -> int:
        """Requests waiting for admission (thread-safe)."""
        with self._lock:
            return len(self._queue)

    def outstanding(self) -> int:
        """Queued + in-flight requests — the load signal a router's
        least-outstanding-requests dispatch reads (thread-safe)."""
        return self.queued() + self.occupied_slots()

    # -- router surface (driver-thread only, except where noted) --------

    def drain_queued(self) -> List[Tuple["_Slot", object]]:
        """Pop every not-yet-admitted request off the queue, returning
        ``(slot, record)`` pairs for :meth:`adopt` on another replica.

        The internal slot travels whole so preemption-recompute state
        (``prefill_seq`` carrying already-generated tokens) survives the
        move; the metrics record is evicted here and re-registered by
        ``adopt`` so TTFT/latency still span from the original submit.
        Must run on the tick-driver thread: admission peeks the queue
        head and pops it in two lock sections, so stealing the queue
        from another thread could race a mid-admission pop.
        """
        with self._lock:
            stolen = list(self._queue)
            self._queue.clear()
        return [(s, self.metrics.evict(s.rid)) for s in stolen]

    def adopt(self, slot: "_Slot", record=None, *, front: bool = False
              ) -> None:
        """Enqueue a slot drained from another replica — same
        :class:`Request`, same ``Future``, same generated-token state.

        The request was already admitted by the tier, so the bounded
        ``queue_limit`` does not apply (shedding it here would drop work
        the client was promised). Assumes replica geometry is uniform
        (same ``max_len``; chunked prefill wherever preempted slots may
        move) — the :class:`repro.serve.router.Router` constructor
        enforces this. Thread-safe.
        """
        budget = int(slot.prompt.size) + slot.req.max_new_tokens
        if budget > self.max_len:
            raise ValueError(
                f"adopted request {slot.rid} needs {budget} tokens but "
                f"this replica's max_len is {self.max_len} — router "
                f"replicas must have uniform geometry")
        with self._lock:
            if (self.metrics.request(slot.rid) is not None
                    or any(s.rid == slot.rid for s in self._queue)):
                raise ValueError(f"rid {slot.rid} is already live on "
                                 f"this replica")
            self._next_rid = max(self._next_rid, slot.rid + 1)
            # the queue span restarts on THIS replica's tracer timeline
            # (timestamps are per-tracer epochs, not transferable)
            slot.trace_t0 = self.tracer.now()
            if record is not None:
                self.metrics.adopt(record)
            else:
                self.metrics.on_submit(slot.rid, int(slot.prompt.size))
            if front:
                self._queue.appendleft(slot)
            else:
                self._queue.append(slot)

    def set_params(self, params) -> None:
        """Hot-swap the model parameters (checkpoint swap on a drained
        replica). Compiled steps are pure functions of the param arrays,
        so no retrace happens as long as shapes/dtypes match — which the
        loader's template-validated restore guarantees. Refuses to swap
        under live requests: a mid-flight swap would splice two
        checkpoints into one output stream."""
        if self.has_work():
            raise RuntimeError(
                "set_params with requests queued or in flight — drain "
                "this engine first (Router.drain + wait_drained)")
        self._params = params

    def abort_all(self, exc: BaseException) -> None:
        """Fail every queued and in-flight request with ``exc``.

        The crash path: when a tick raises (bad extras, an arch the pool
        can't serve, a device error), whoever drives the loop calls this so
        every outstanding future resolves with the real error instead of
        hanging until its timeout. The pool is left empty (pages freed for
        recycling); the engine itself stays usable for new submissions.
        """
        with self._lock:
            dead = list(self._queue)
            self._queue.clear()
            self._cancels.clear()
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                self._release_slot(i, s)
                dead.append(s)
        self.metrics.sync_pool(self.pool)
        if dead:
            self.tracer.instant("abort", pid=self.replica,
                                tid=TRACK_ENGINE, count=len(dead),
                                error=repr(exc))
        for s in dead:
            self.metrics.evict(s.rid)
            if not s.future.done():
                s.future.set_exception(exc)

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a queued or in-flight request.

        Thread-safe. Returns ``True`` when ``rid`` is currently queued or
        occupying a slot; the cancellation is processed at the next tick
        boundary (slot and pool state belong to the single driver
        thread): the request's future resolves with
        :class:`RequestCancelled` and its slot + pages free right there —
        an abandoned request can no longer hold capacity. A rid that
        finishes between this call and the boundary is a harmless no-op.
        """
        with self._lock:
            known = any(s.rid == rid for s in self._queue)
        known = known or any(s is not None and s.rid == rid
                             for s in self._slots)
        if not known:
            return False
        with self._lock:
            self._cancels.add(rid)
        return True

    def active_requests(self) -> List[int]:
        return [s.rid for s in self._slots if s is not None]

    def devices(self) -> set:
        """Devices holding this engine's weights and cache pool."""
        return {d for leaf in jax.tree_util.tree_leaves(
                    (self._params, self._caches))
                for d in leaf.devices()}

    @property
    def compile_stats(self) -> Dict:
        return {"compiles": self.compile_cache.compiles,
                "traces": dict(self.compile_cache.traces)}

    def reset_metrics(self) -> None:
        """Fresh metrics (tick clock included) without touching compiled
        state or the pool's *allocations* — a benchmark warms every
        bucket, resets, then measures a compile-free steady state. Only
        valid while no request is in flight (in-flight RequestMetrics
        would be orphaned).

        Rebases everything burn-in could have inflated: the pool's
        high-water stats (``pages_hwm`` used to survive reset through
        ``sync_pool`` re-importing the allocator's stale ``_hwm`` — the
        regression test in ``tests/test_obs.py`` pins the fix) and the
        tracer ring (burn-in spans would pollute the exported timeline).
        Registry callbacks read through ``self``, so the object swap is
        invisible to the unified telemetry surface.
        """
        if self.has_work():
            raise RuntimeError("reset_metrics with requests in flight")
        self.pool.reset_stats()
        self.tracer.clear()
        self._name_tracks()          # clear() drops the track-name maps
        self.metrics = self._fresh_metrics(
            history=self.metrics.max_request_history)
        self.metrics.sync_pool(self.pool)

    # -- the tick loop -------------------------------------------------

    def step(self) -> int:
        """One engine tick: process cancellations and deadlines, admit
        into free slots, grow/preempt page tables (incremental
        admission), advance chunked prefills by one chunk, then one
        pooled decode. Returns the number of slots still active after
        the tick."""
        tick = self.metrics.ticks
        t_wall = time.monotonic()
        tt0 = self.tracer.now()
        with self._phase("serve.admit"):
            self._process_cancels()
            self._expire_deadlines()
            self._admit()
            self.metrics.on_occupancy(
                sum(s is not None for s in self._slots))
            if self.faults is not None:
                # the mid-tick crash site: admissions landed, compute has
                # not run — exactly where a device error would strand
                # futures if the driver's abort path were broken
                self.faults.check("engine.tick")
            if self.admission == "incremental":
                self._grow_pages()
        if self.prefill_chunk is not None:
            self._chunk_tick()
        if any(s is not None and s.decoding for s in self._slots):
            self._decode_tick()
        self.metrics.on_tick()
        active = sum(s is not None for s in self._slots)
        self.tracer.complete("tick", tt0, self.tracer.now(),
                             pid=self.replica, tid=TRACK_ENGINE,
                             tick=tick, active=active)
        self._tick_hist.observe(time.monotonic() - t_wall)
        return active

    def run_until_idle(self, max_ticks: int = 100_000) -> int:
        """Drive ticks until queue and pool drain; returns ticks spent."""
        start = self.metrics.ticks
        while self.has_work():
            self.step()
            if self.metrics.ticks - start > max_ticks:
                raise RuntimeError(
                    f"engine did not drain within {max_ticks} ticks "
                    f"(active={self.active_requests()})")
        return self.metrics.ticks - start

    # -- internals -----------------------------------------------------

    def _phase(self, name: str, **args):
        """One leaf of the tick's host work (``serve.*``): a profiler
        annotation, and a span on the engine lane when tracing."""
        return self.tracer.phase(name, pid=self.replica,
                                 tick=self.metrics.ticks, **args)

    def _open_lane(self, slot: _Slot, name: str, t: float,
                   **args) -> None:
        """Start the request lane's ``prefill`` or ``decode`` span at
        ``t``, ending the one before it there."""
        self._close_lane(slot, t)
        slot.lane = (name, t, args)

    def _close_lane(self, slot: _Slot, t: Optional[float] = None) -> None:
        """End the request lane's open span, if any, at ``t`` (now): one
        span per request and phase, whatever the number of ticks."""
        if slot.lane is None:
            return
        name, t0, args = slot.lane
        slot.lane = None
        self.tracer.complete(name, t0, self.tracer.now() if t is None else t,
                             pid=self.replica, tid=slot.rid + 1,
                             rid=slot.rid, new_tokens=len(slot.tokens),
                             **args)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _admit(self) -> None:
        while True:
            idx = self._free_slot()
            if idx is None:
                return
            with self._lock:
                if not self._queue:
                    return
                slot = self._queue[0]
            if self.admission == "incremental":
                # prompt-only reservation; the decode budget grows page-
                # by-page in _grow_pages as the slot actually decodes
                budget = self._n_front + int(slot.prefill_seq.size)
            else:
                budget = (self._n_front + int(slot.prefill_seq.size)
                          + slot.req.max_new_tokens)
            try:
                self.pool.alloc_pages(idx, budget)
            except PoolExhausted:
                # keep FIFO order: the head request waits for pages freed
                # by finishing slots; admission retries every tick
                self.metrics.on_pool_exhausted()
                return
            with self._lock:
                self._queue.popleft()
            self.metrics.sync_pool(self.pool)
            self._admit_one(slot, idx)

    def _admit_one(self, slot: _Slot, idx: int) -> None:
        self.metrics.on_admit(slot.rid)
        tid = slot.rid + 1
        tnow = self.tracer.now()
        self.tracer.name_track(self.replica, tid, f"req {slot.rid}")
        self.tracer.complete("queue", slot.trace_t0, tnow,
                             pid=self.replica, tid=tid, rid=slot.rid,
                             resume=bool(slot.tokens))
        self.tracer.instant("admit", pid=self.replica, tid=tid, ts=tnow,
                            rid=slot.rid, slot=idx,
                            tick=self.metrics.ticks)
        slot.admit_seq = self._admit_seq
        self._admit_seq += 1
        if self.prefill_chunk is not None:
            # chunked admission: no prefill work here — the chunk tick(s)
            # stream the prompt through the pool
            slot.prefilled = 0
            self._slots[idx] = slot
            return
        self._admit_bucketed(slot, idx)

    def _admit_bucketed(self, slot: _Slot, idx: int) -> None:
        """Whole-prompt admission (dense pools and non-chunkable archs):
        right-pad to a bucket, prefill at batch 1, splice into the pool.
        Prefills ``prefill_seq`` (== ``prompt`` except after a preemption)
        so a resumed slot recomputes its full prefix."""
        req = slot.req
        plen = int(slot.prefill_seq.size)
        bucket = self.bucket_for(plen)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = slot.prefill_seq
        batch = {"tokens": jnp.asarray(tokens)}
        if req.extras:
            batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
        last_pos = jnp.asarray([plen - 1], jnp.int32)
        t0 = time.monotonic()
        tt0 = self.tracer.now()
        with self._scope():
            logits, sub = self._prefill_fn(bucket)(self._params, batch,
                                                   last_pos)
            insert_args = [self._caches, sub, jnp.asarray(idx, jnp.int32)]
            if self.pool.kind == "paged":
                insert_args.append(self.pool.page_row(idx))
            self._caches = self._insert_fn()(*insert_args)
            tok = int(self._first_token_fn()(
                logits, jax.random.fold_in(self._key, slot.rid))[0])
        self.metrics.on_prefill_work(plen, time.monotonic() - t0)
        tt1 = self.tracer.now()
        self.tracer.complete("prefill", tt0, tt1,
                             pid=self.replica, tid=slot.rid + 1,
                             rid=slot.rid, bucket=bucket, tokens=plen,
                             recompute=bool(slot.tokens))
        self._open_lane(slot, "decode", tt1)
        if slot.tokens:
            # resumed after preemption: this prefill recomputed an
            # already-counted prefix, and the sampled token is the NEXT
            # one — bumping `prefills` or re-firing on_first_token here
            # would inflate the prefill count and reset new_tokens/TTFT
            self.metrics.on_token(slot.rid)
            slot.tokens.append(tok)
        else:
            self.metrics.on_prefill_done()
            self.metrics.on_first_token(slot.rid)
            self.tracer.instant("first_token", pid=self.replica,
                                tid=slot.rid + 1, rid=slot.rid,
                                tick=self.metrics.ticks)
            slot.tokens = [tok]
        slot.last_token = tok
        slot.cur_pos = self._n_front + plen
        self._slots[idx] = slot
        if self._finished(slot):
            self._finish(idx)

    # -- lifecycle: cancel / deadline / preempt -------------------------

    def _resolve_dead(self, dead: List[Tuple[_Slot, BaseException]],
                      on_record: Callable[[int], None]) -> None:
        """Shared tail of the cancel/deadline paths: evict the metrics
        record and fail the future."""
        for s, exc in dead:
            on_record(s.rid)
            if not s.future.done():
                s.future.set_exception(exc)

    def _process_cancels(self) -> None:
        """Resolve every pending ``cancel(rid)``: queued requests leave
        the queue, in-flight ones free their slot and pages immediately.
        Unknown/already-finished rids are no-ops."""
        with self._lock:
            if not self._cancels:
                return
            rids, self._cancels = self._cancels, set()
            hit = [s for s in self._queue if s.rid in rids]
            for s in hit:
                self._queue.remove(s)
        for i, s in enumerate(self._slots):
            if s is not None and s.rid in rids:
                self._slots[i] = None
                self._release_slot(i, s)
                hit.append(s)
        if hit:
            self.metrics.sync_pool(self.pool)
        for s in hit:
            self.tracer.instant("cancel", pid=self.replica,
                                tid=s.rid + 1, rid=s.rid,
                                tick=self.metrics.ticks)
        self._resolve_dead([(s, RequestCancelled(s.rid)) for s in hit],
                           self.metrics.on_cancel)

    def _deadline_reason(self, slot: _Slot) -> Optional[str]:
        req = slot.req
        if req.deadline_ticks is None and req.deadline_s is None:
            return None
        rm = self.metrics.request(slot.rid)
        if rm is None:
            return None
        if req.deadline_ticks is not None:
            waited = self.metrics.ticks - rm.submit_tick
            if waited >= req.deadline_ticks:
                return (f"{waited} ticks since submit >= deadline_ticks="
                        f"{req.deadline_ticks}")
        if req.deadline_s is not None:
            waited_s = self.metrics.clock() - rm.submit_t
            if waited_s >= req.deadline_s:
                return (f"{waited_s:.3f}s since submit >= deadline_s="
                        f"{req.deadline_s}")
        return None

    def _expire_deadlines(self) -> None:
        """Fail every queued or in-flight request past its deadline with
        :class:`DeadlineExceeded`, freeing slots and pages — a stuck or
        abandoned request cannot hold capacity forever."""
        with self._lock:
            expired = [(s, self._deadline_reason(s)) for s in self._queue]
            expired = [(s, r) for s, r in expired if r is not None]
            for s, _ in expired:
                self._queue.remove(s)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            r = self._deadline_reason(s)
            if r is not None:
                self._slots[i] = None
                self._release_slot(i, s)
                expired.append((s, r))
        if expired:
            self.metrics.sync_pool(self.pool)
        for s, r in expired:
            self.tracer.instant("deadline", pid=self.replica,
                                tid=s.rid + 1, rid=s.rid, reason=r,
                                tick=self.metrics.ticks)
        self._resolve_dead(
            [(s, DeadlineExceeded(s.rid, r)) for s, r in expired],
            self.metrics.on_deadline)

    def _preempt(self, idx: int) -> None:
        """Kick slot ``idx`` for pages: free its pages and re-queue the
        request at the queue head with its already-generated tokens
        appended to the prompt. Re-admission recomputes the whole prefix
        through the ordinary chunked-prefill path; greedy decoding makes
        the resumed output token-identical to a never-preempted run."""
        s = self._slots[idx]
        self._slots[idx] = None
        self._release_slot(idx, s)
        computed = (s.prefilled if s.prefilling
                    else int(s.prompt.size) + len(s.tokens))
        if s.tokens:
            s.prefill_seq = np.concatenate(
                [s.prompt, np.asarray(s.tokens, np.int32)])
        else:
            s.prefill_seq = s.prompt
        s.prefilled = -1
        s.cur_pos = 0
        s.last_token = -1
        s.anchor = None          # recompute re-derives it (final chunk)
        self.metrics.on_preempt(s.rid, computed)
        self.tracer.instant("preempt", pid=self.replica, tid=s.rid + 1,
                            rid=s.rid, computed=computed,
                            tick=self.metrics.ticks)
        s.trace_t0 = self.tracer.now()   # back in the queue: new span
        with self._lock:
            self._queue.appendleft(s)

    def _grow_pages(self) -> None:
        """Incremental admission: grow every live slot's page table to
        cover this tick's cache writes, oldest slot first; on
        :class:`PoolExhausted` preempt the *youngest* slot and retry.
        Terminates: every preemption frees pages, the growing slot may
        end up preempting itself, and ``submit()`` already rejected any
        request whose full budget could never fit the pool."""
        C = self.prefill_chunk
        order = sorted(
            (i for i, s in enumerate(self._slots) if s is not None),
            key=lambda i: self._slots[i].admit_seq)
        tt0 = self.tracer.now()
        for i in order:
            s = self._slots[i]
            if s is None:                  # preempted as a younger victim
                continue
            # under speculation a decode tick writes spec_k extra draft
            # positions past the committed one; grow to cover them, but
            # never past the request's own budget — overshoot beyond it
            # routes to the trash page and needs no pages
            budget = (self._n_front + int(s.prompt.size)
                      + s.req.max_new_tokens)
            if s.prefilling:
                end = min(s.prefilled + C, int(s.prefill_seq.size))
                need = self._n_front + end
                if end == s.prefill_seq.size:
                    # final chunk lands this tick: the slot joins this
                    # very tick's decode, writing one position further
                    # (plus its draft positions when speculating)
                    need = min(need + 1 + self.spec_k, budget)
            else:
                # this tick's decode write (+ draft positions)
                need = min(s.cur_pos + 1 + self.spec_k, budget)
            while True:
                try:
                    self.pool.alloc_pages(i, need)
                    break
                except PoolExhausted:
                    self.metrics.on_pool_exhausted()
                    victim = max(
                        (j for j, v in enumerate(self._slots)
                         if v is not None),
                        key=lambda j: self._slots[j].admit_seq)
                    self._preempt(victim)
                    if victim == i:
                        break              # kicked ourselves; slot is gone
        self.metrics.sync_pool(self.pool)
        if order:
            self.tracer.complete("grow_pages", tt0, self.tracer.now(),
                                 pid=self.replica, tid=TRACK_ENGINE,
                                 tick=self.metrics.ticks,
                                 pages_in_use=self.pool.pages_in_use)

    def _chunk_tick(self) -> None:
        """Advance every prefilling slot by one prompt chunk (one pooled
        call). Slots whose final chunk lands sample their first token off
        the chunk logits and join this very tick's decode."""
        live = [(i, s) for i, s in enumerate(self._slots)
                if s is not None and s.prefilling]
        if not live:
            return
        C = self.prefill_chunk
        spans = {}
        with self._phase("serve.chunk", slots=len(live)):
            tt0 = self.tracer.now()
            tokens = np.zeros((self.slots, C), np.int32)
            start = np.zeros((self.slots,), np.int32)
            last = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            for i, s in live:
                lo = s.prefilled
                hi = min(lo + C, int(s.prefill_seq.size))
                tokens[i, :hi - lo] = s.prefill_seq[lo:hi]
                start[i] = lo
                last[i] = hi - lo - 1
                active[i] = True
                spans[i] = (lo, hi)
                if lo == 0:
                    self._open_lane(s, "prefill", tt0,
                                    tokens=int(s.prefill_seq.size),
                                    recompute=bool(s.tokens))
            t0 = time.monotonic()
            with self._scope():
                logits, h_last, self._caches = self._chunk_fn()(
                    self._params, jnp.asarray(tokens), self._caches,
                    jnp.asarray(start), jnp.asarray(last),
                    jnp.asarray(active), self.pool.gather_args()["page_table"])
        real = sum(hi - lo for lo, hi in spans.values())
        self.metrics.on_prefill_work(real, time.monotonic() - t0,
                                     chunked=True)
        for i, s in live:
            s.prefilled = spans[i][1]
        done = [(i, s) for i, s in live if not s.prefilling]
        if not done:
            return
        finishers = []
        with self._phase("serve.first_token", slots=len(done)):
            anchors = np.asarray(h_last) if self.spec_k else None
            for i, s in done:
                with self._scope():
                    tok = int(self._first_token_fn()(
                        logits[i:i + 1],
                        jax.random.fold_in(self._key, s.rid))[0])
                if s.tokens:
                    # resumed after preemption: the recomputed prefix
                    # already ends in generated tokens, so this is the
                    # NEXT token — and the request's one real prefill was
                    # already counted, so on_prefill_done would inflate
                    # `prefills`
                    self.metrics.on_token(s.rid)
                else:
                    self.metrics.on_prefill_done()
                    self.metrics.on_first_token(s.rid)
                    self.tracer.instant("first_token", pid=self.replica,
                                        tid=s.rid + 1, rid=s.rid,
                                        tick=self.metrics.ticks)
                self._open_lane(s, "decode", self.tracer.now())
                s.tokens.append(tok)
                s.last_token = tok
                s.cur_pos = self._n_front + int(s.prefill_seq.size)
                s.prefilled = -1                # decode phase
                if anchors is not None:
                    s.anchor = anchors[i]       # draft seed for this tick
                if self._finished(s):
                    finishers.append(i)
            for i in finishers:
                self._finish(i)

    def _finished(self, slot: _Slot) -> bool:
        if len(slot.tokens) >= slot.req.max_new_tokens:
            return True
        stop = slot.req.stop_token
        return stop is not None and slot.last_token == stop

    def _release_slot(self, idx: int, slot: _Slot) -> None:
        """The ONE scrub-then-free tail for every slot-exit path — finish,
        cancel, deadline, preempt, abort — which also ends ``slot``'s open
        lane span. Under ``scrub_freed_slots`` the
        slot's cache state is re-initialized BEFORE ``pool.free()``: after
        free() the slot's page-table row points at trash, so a late scrub
        would zero the trash page while the request's real KV survived in
        recycled pages (the stale-KV scrub-bypass bug the lifecycle paths
        used to have)."""
        self._close_lane(slot)
        if self.scrub_freed_slots:
            with self._scope():
                reset_args = [self._caches, jnp.asarray(idx, jnp.int32)]
                if self.pool.kind == "paged":
                    reset_args.append(self.pool.page_row(idx))
                self._caches = self._reset_fn()(*reset_args)
        self.pool.free(idx)

    def _finish(self, idx: int) -> None:
        slot = self._slots[idx]
        self._slots[idx] = None
        rm = self.metrics.on_finish(slot.rid)
        self._release_slot(idx, slot)
        self.metrics.sync_pool(self.pool)
        self.tracer.instant("finish", pid=self.replica,
                            tid=slot.rid + 1, rid=slot.rid,
                            new_tokens=len(slot.tokens),
                            tick=self.metrics.ticks)
        slot.future.set_result(GenerationResult(
            rid=slot.rid, prompt=slot.prompt,
            tokens=list(slot.tokens), metrics=rm))

    def _decode_tick(self) -> None:
        if self.spec_k:
            return self._spec_decode_tick()
        with self._phase("serve.decode"):
            tokens = np.zeros((self.slots,), np.int32)
            cur_pos = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            for i, s in enumerate(self._slots):
                if s is None or s.prefilling:
                    continue
                tokens[i] = s.last_token
                cur_pos[i] = s.cur_pos
                active[i] = True
            n_active = int(active.sum())
            rng = jax.random.fold_in(self._key,
                                     0x5E57E9 + self.metrics.ticks)
            t0 = time.monotonic()
            step_args = [self._params, jnp.asarray(tokens), self._caches,
                         jnp.asarray(cur_pos), rng, jnp.asarray(active)]
            if self.pool.kind == "paged":
                step_args.append(self.pool.gather_args()["page_table"])
            with self._scope():
                nxt, self._caches = self._decode_fn()(*step_args)
        with self._phase("serve.readback", active=n_active):
            nxt = np.asarray(nxt)
        with self._phase("serve.emit"):
            self.metrics.on_decode_tick(n_active, n_active,
                                        time.monotonic() - t0)
            for i, s in enumerate(self._slots):
                if s is None or s.prefilling:
                    continue
                tok = int(nxt[i])
                s.tokens.append(tok)
                s.last_token = tok
                s.cur_pos += 1
                self.metrics.on_token(s.rid)
                if self._finished(s):
                    self._finish(i)

    def _spec_decode_tick(self) -> None:
        """Draft-k-verify-1: propose ``spec_k`` tokens per slot off each
        slot's residual-stream anchor, verify every position in ONE
        batched full-model pass, commit each slot's accepted prefix.

        The committed stream is the verify pass's own greedy targets —
        position by position exactly what non-speculative decode would
        have sampled — so acceptance only decides how many land per tick,
        never which tokens. A commit truncated below the accepted length
        (budget or stop token) always finishes the slot, so the verify
        anchor (valid only for full commits) is never used stale.

        Phases: ``serve.decode`` holds both dispatches and the drafts'
        read-back between them, ``serve.readback`` the verify results'.
        """
        live = [(i, s) for i, s in enumerate(self._slots)
                if s is not None and s.decoding]
        if not live:
            return
        K1 = self.spec_k + 1
        with self._phase("serve.decode", slots=len(live)):
            tokens = np.zeros((self.slots, K1), np.int32)
            cur_pos = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            anchors = np.zeros((self.slots, self.cfg.d_model), np.float32)
            for i, s in live:
                tokens[i, 0] = s.last_token
                cur_pos[i] = s.cur_pos
                active[i] = True
                anchors[i] = s.anchor
            t0 = time.monotonic()
            tt0 = self.tracer.now()
            with self._scope():
                drafts = self._draft_fn()(self._params,
                                          jnp.asarray(anchors),
                                          jnp.asarray(tokens[:, 0]))
                tokens[:, 1:] = np.asarray(drafts)
                ttd = self.tracer.now()
                targets, accepted, anchor_out, self._caches = \
                    self._spec_verify_fn()(
                        self._params, jnp.asarray(tokens), self._caches,
                        jnp.asarray(cur_pos), jnp.asarray(active),
                        self.pool.gather_args()["page_table"])
            tt1 = self.tracer.now()
        self.tracer.complete("spec_draft", tt0, ttd, pid=self.replica,
                             tid=TRACK_ENGINE, slots=len(live),
                             tick=self.metrics.ticks)
        self.tracer.complete("spec_verify", ttd, tt1, pid=self.replica,
                             tid=TRACK_ENGINE, slots=len(live),
                             tick=self.metrics.ticks)
        with self._phase("serve.readback", slots=len(live)):
            targets = np.asarray(targets)
            accepted = np.asarray(accepted)
            anchor_out = np.asarray(anchor_out)
        with self._phase("serve.emit"):
            committed_total = 0
            for i, s in live:
                m = int(accepted[i]) + 1
                m = min(m, s.req.max_new_tokens - len(s.tokens))
                toks = [int(t) for t in targets[i, :m]]
                stop = s.req.stop_token
                if stop is not None and stop in toks:
                    toks = toks[:toks.index(stop) + 1]
                s.tokens.extend(toks)
                s.last_token = toks[-1]
                s.cur_pos += len(toks)
                s.anchor = anchor_out[i]
                committed_total += len(toks)
                self.metrics.on_token(s.rid, len(toks))
                self.tracer.complete("spec", tt0, tt1, pid=self.replica,
                                     tid=s.rid + 1, rid=s.rid,
                                     drafted=self.spec_k,
                                     accepted=int(accepted[i]),
                                     committed=len(toks))
            self.metrics.on_spec_tick(
                drafted=len(live) * self.spec_k,
                accepted=int(accepted[[i for i, _ in live]].sum()))
            self.metrics.on_decode_tick(len(live), committed_total,
                                        time.monotonic() - t0)
            for i, s in live:
                if self._finished(s):
                    self._finish(i)
