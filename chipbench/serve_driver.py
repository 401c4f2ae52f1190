"""The serving driver (traffic driver ``serve``): one engine behind
``ServeClient``, offered an open-loop load at the mix's fixed rate.

Set-up makes the weights, builds the engine, warms the one chunked-
prefill, decode and first-token programs that the traffic uses, and
resets the engine's counters. The window offers every request due in
``[0, seconds)``; the run then drains them. Latencies run from each
request's due time, so a late generator shows as latency.
"""

from __future__ import annotations

import gc
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import gen, reference, stats, weights


class CompileLog:
    """What reached JAX's compiler in this process, counted from its
    ``jax.monitoring`` events, each with its seconds: ``compiles``, the
    programs handed to the backend (compiled, or loaded from the
    persistent compilation cache), ``cache_loads``, those of them the
    cache served, and ``traces``, the functions traced to a jaxpr.
    ``mark()`` and ``since()`` give one phase's part (set-up, window,
    reference)."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_loads",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax

        self.total = {k: 0.0 for v in self.EVENTS.values()
                      for k in (v, v + "_s")}

        def on(event, duration, **_):
            name = self.EVENTS.get(event)
            if name is not None:
                self.total[name] += 1
                self.total[name + "_s"] += duration

        jax.monitoring.register_event_duration_secs_listener(on)

    def mark(self) -> Dict[str, float]:
        return dict(self.total)

    def since(self, mark: Dict[str, float]) -> Dict[str, float]:
        return {k: v - mark[k] for k, v in self.total.items()}


def _engine_factory(cfg, traffic: Dict):
    from repro.serve import ServeEngine

    eng = traffic["engine"]

    def make(i, params, context):
        return ServeEngine(cfg, params, slots=eng["slots"],
                           max_len=eng["max_len"], pool="paged",
                           page_size=eng["page_size"],
                           prefill_chunk=eng["prefill_chunk"],
                           admission=eng["admission"],
                           spec_k=eng["spec_k"], seed=0, replica=i,
                           context=context)
    return make


def _tokens_in(first: float, finish: float, n: int, t0: float,
               t1: float) -> int:
    """Output tokens of one request emitted in ``[t0, t1)``: the first at
    ``first``, the others evenly over ``(first, finish]``."""
    if n <= 0:
        return 0
    times = [first] if n == 1 else list(
        first + (finish - first) * np.arange(n) / (n - 1))
    return int(sum(t0 <= t < t1 for t in times))


class Window:
    """The measured part of one run and what it leaves to read."""

    def __init__(self):
        self.requests: List[Dict] = []
        self.t0 = self.t1 = 0.0
        self.trace: Optional[Dict] = None
        self.compiles = 0
        self.log: Dict[str, float] = {}      # CompileLog over the window
        self.host: Dict[str, float] = {}     # CPU seconds, engine ticks


def build(cell, seed: int, backend: Optional[str] = None,
          phases: Optional[Dict[str, float]] = None):
    """Weights and a started engine for ``cell``; returns
    ``(cfg, params, engines, submit, front)``, ``engines`` a list of one.
    ``phases`` gets the seconds spent making the weights and the engine."""
    import jax
    from repro.launch.serve import build_replicas
    from repro.models import lm
    from repro.runtime import pytree as pt
    from repro.serve import ServeClient
    from chipbench import manifest

    phases = {} if phases is None else phases
    t = time.monotonic()
    cfg = manifest.model_config(cell.config, backend)
    shapes = pt.abstract_params(lm.model_specs(cfg))
    params = jax.block_until_ready(
        weights.make(shapes, seed, device=jax.devices()[0]))
    phases["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    engines = build_replicas(1, cfg, params,
                             _engine_factory(cfg, cell.traffic))
    front = ServeClient(engines[0])
    phases["engine_s"] = time.monotonic() - t
    return cfg, params, engines, front.submit, front


def warm(engines, submit, cfg, traffic: Dict, seed: int) -> None:
    """One request per replica with a prompt of two chunks: compiles the
    chunked-prefill, decode and first-token programs, and nothing else."""
    from repro.serve import Request

    chunk = traffic["engine"]["prefill_chunk"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    futs = [submit(Request(prompt=tuple(int(t) for t in rng.integers(
        0, cfg.vocab_size, size=chunk + 1)), max_new_tokens=2))
        for _ in engines]
    for f in futs:
        f.result(timeout=1200)
    for e in engines:
        if not e.compile_stats["compiles"]:
            raise RuntimeError(f"replica {e.replica} was not warmed")
        e.reset_metrics()


TICKS = ("ticks", "chunk_ticks", "decode_steps")


def _ticks(engines) -> Dict[str, int]:
    """The engines' tick counters, summed (``EngineMetrics``)."""
    return {k: sum(int(getattr(e.metrics, k)) for e in engines)
            for k in TICKS}


def measure(cell, engines, submit, cfg, seed: int, seconds: float,
            trace: bool, compiles: CompileLog,
            drain_s: float) -> Window:
    """Offer the window's requests, drain them, and collect the stamps,
    what reached the compiler, this process's CPU seconds and the
    engines' ticks."""
    import jax
    from repro.serve import Request

    traffic = cell.traffic
    items = gen.generate(traffic, seed, seconds, cfg.vocab_size)
    w = Window()
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    t_start = min(traffic["trace_start_s"], seconds / 4)
    t_len = min(traffic["trace_s"], seconds - t_start)
    prof = {"on": False, "t": [0.0, 0.0]}

    def between(now):
        if not trace:
            return
        if not prof["on"] and prof["t"][0] == 0 and now >= w.t0 + t_start:
            jax.profiler.start_trace(tdir, profiler_options=_options())
            prof["on"], prof["t"][0] = True, time.monotonic()
        elif prof["on"] and now >= w.t0 + t_start + t_len:
            jax.profiler.stop_trace()
            prof["on"], prof["t"][1] = False, time.monotonic()

    mark, ticks = compiles.mark(), _ticks(engines)
    cpu0 = time.process_time()
    w.t0 = time.monotonic()
    futs, _ = gen.replay(
        submit, items, w.t0,
        lambda it: Request(prompt=it.prompt,
                           max_new_tokens=it.max_new_tokens),
        between=between)
    while time.monotonic() < w.t0 + seconds:
        between(time.monotonic())
        time.sleep(min(0.05, max(0.0, w.t0 + seconds - time.monotonic())))
    w.t1 = w.t0 + seconds
    if prof["on"]:
        jax.profiler.stop_trace()
        prof["t"][1] = time.monotonic()
    w.log = compiles.since(mark)
    w.compiles = int(w.log["compiles"])
    w.host = {"cpu_s": time.process_time() - cpu0}
    w.host.update((k, v - ticks[k]) for k, v in _ticks(engines).items())
    deadline = w.t1 + drain_s
    for it, f in zip(items, futs):
        rec = {"due": w.t0 + it.due_s, "prompt": it.prompt,
               "max_new": it.max_new_tokens, "ok": False}
        if f is not None:
            try:
                res = f.result(timeout=max(0.0, deadline - time.monotonic()))
                rm = res.metrics
                rec.update(ok=len(res.tokens) == it.max_new_tokens,
                           tokens=list(res.tokens), admit=rm.admit_t,
                           first=rm.first_token_t, finish=rm.finish_t,
                           submit=rm.submit_t)
            except Exception as e:         # errored or still running
                rec["error"] = repr(e)
        w.requests.append(rec)
    if trace:
        from chipbench import xplane
        w.trace = xplane.reduce_file(xplane.find_xplane(tdir),
                                     prof["t"][1] - prof["t"][0])
        _rmtree(tdir)
    return w


def _options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _rmtree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)


def end_to_end(w: Window, seconds: float, drain_s: float) -> Dict:
    """ttft/tpot tails over every request due in the window (a failed one
    misses every limit: it counts as the drain limit) and output tokens
    per second inside the window."""
    miss = (w.t1 - w.t0 + drain_s)
    ttft, tpot, toks = [], [], 0
    for r in w.requests:
        if not r["ok"]:
            ttft.append(miss)
            tpot.append(miss)
            continue
        n = len(r["tokens"])
        ttft.append(r["first"] - r["due"])
        tpot.append((r["finish"] - r["first"]) / max(n - 1, 1))
        toks += _tokens_in(r["first"], r["finish"], n, w.t0, w.t1)
    return {"ttft_p95_ms": stats.percentile(ttft, 0.95) * 1e3,
            "tpot_p95_ms": stats.percentile(tpot, 0.95) * 1e3,
            "gen_tok_s": toks / seconds}


def sample(w: Window, seed: int, max_requests: int) -> List[Dict]:
    """Finished requests to compare, drawn from the seed: the one with the
    most served tokens, then others in a seeded order, up to
    ``max_requests``."""
    done = [r for r in w.requests if r["ok"]]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    order = [longest] + [int(i) for i in rng.permutation(len(done))
                         if i != longest]
    return [done[i] for i in order[:max_requests]]


def compare(cell, params, picked: List[Dict], quant: Optional[str] = None
            ) -> Dict:
    """The widest and the mean gap of a served token below the reference's
    best logit over the sampled requests (and the control's, with
    ``quant``)."""
    mc = cell.config
    length = cell.traffic["engine"]["max_len"]
    acc = {"max": 0.0, "sum": 0.0, "control_max": 0.0, "control_sum": 0.0,
           "tokens": 0}
    for r in picked:
        g = reference.served_gaps(mc, params, r["prompt"], r["tokens"],
                                  length, quant)
        for k in acc:
            acc[k] = max(acc[k], g[k]) if k.endswith("max") else \
                acc[k] + g[k]
    n = max(acc["tokens"], 1)
    return {"served_logit_gap": acc["max"],
            "mean_logit_gap": acc["sum"] / n,
            "control_logit_gap": acc["control_max"],
            "control_mean_gap": acc["control_sum"] / n,
            "compared_tokens": acc["tokens"],
            "compared_requests": len(picked)}


def verdict(cmp: Dict, unfinished: int, window_compiles: int,
            limits: Dict[str, float], min_tokens: int):
    """``(correct, checks)``: each number compared beside its limit. The
    compared tokens are held from below, everything else from above; a
    number without a limit gets -1, which no reading meets."""
    checks = {k: (cmp[k], limits.get(k, -1.0))
              for k in ("served_logit_gap", "mean_logit_gap")}
    checks["compared_tokens"] = (cmp["compared_tokens"], float(min_tokens))
    checks["unfinished"] = (unfinished, 0.0)
    checks["window_compiles"] = (window_compiles, 0.0)
    correct = (all(v <= l for k, (v, l) in checks.items()
                   if k != "compared_tokens")
               and cmp["compared_tokens"] >= min_tokens)
    return correct, checks


def control_readings(cmp: Dict) -> Dict:
    """The control's numbers under the program's names, for ``verdict``."""
    return {"served_logit_gap": cmp["control_logit_gap"],
            "mean_logit_gap": cmp["control_mean_gap"],
            "compared_tokens": cmp["compared_tokens"]}


def peak_bytes(devices) -> int:
    """The most device memory a chip held: its peak of live buffers plus
    its peak reserved for the programs' temporaries. On TPU
    ``peak_bytes_in_use`` counts buffers only; a program's temporaries
    show in ``peak_bytes_reserved``, which stays held once reserved."""
    peaks = []
    for d in devices:
        s = d.memory_stats() or {}
        peaks.append(s.get("peak_bytes_in_use", 0)
                     + s.get("peak_bytes_reserved", 0))
    return int(max(peaks)) if peaks else 0


def run(cell, seed: int, seconds: float, trace: bool, *,
        backend: Optional[str] = None, limits: Dict[str, float],
        t_start: float) -> Dict:
    """One run of a serving cell; returns the raw pieces the harness turns
    into its result line."""
    compiles = CompileLog()
    phases = {}
    cfg, params, engines, submit, front = build(cell, seed, backend, phases)
    t = time.monotonic()
    warm(engines, submit, cfg, cell.traffic, seed)
    phases["warm_s"] = time.monotonic() - t
    drain_s = float(cell.traffic["drain_s"])
    setup_s = time.monotonic() - t_start
    log = {"setup": compiles.mark()}
    w = measure(cell, engines, submit, cfg, seed, seconds, trace, compiles,
                drain_s)
    devices = sorted({d for e in engines for d in e.devices()},
                     key=lambda d: d.id)
    mem = peak_bytes(devices)
    front.close()
    del engines, front, submit
    gc.collect()
    after = {"drain_s": time.monotonic() - w.t1}
    ref = cell.traffic["reference"]
    picked = sample(w, seed, ref["max_requests"])
    t, mark = time.monotonic(), compiles.mark()
    cmp = compare(cell, params, picked)
    after["reference_s"] = time.monotonic() - t
    log.update(window=w.log, reference=compiles.since(mark))
    unfinished = sum(not r["ok"] for r in w.requests)
    correct, checks = verdict(cmp, unfinished, w.compiles, limits,
                              ref["min_tokens"])
    e2e = end_to_end(w, seconds, drain_s)
    e2e["setup_s"] = setup_s
    return {"correct": correct, "attempted": len(w.requests),
            "failed": unfinished, "e2e": e2e, "window": w,
            "devices": devices, "memory_peak_bytes": mem, "checks": checks,
            "trace": w.trace, "setup_phases": phases,
            "after_window": after, "compile_log": log,
            "host": w.host}
