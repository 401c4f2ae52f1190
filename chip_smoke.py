#!/usr/bin/env python3
"""Bring-up check on a TPU: serve and train ``smollm-135m-butterfly`` (the
paper's butterfly sandwich on the LM head and MLP projections) at the full
published width, with the fused Pallas kernels compiled for the chip.

    python chip_smoke.py               # one chip: serve, reference, train
    python chip_smoke.py --four-chips  # four chips: multi-chip paths only

One chip, four phases, each printing one line:

* serve — seeded fresh weights (``loader.load_for_serving``, no checkpoint)
  behind a one-replica ``Router`` built as ``python -m repro.launch.serve``
  builds it: paged pool, 8 slots, ``max_len`` 2048, 8 requests from
  ``serve.trace`` with 64-1024-token prompts (some span several prefill
  chunks) and 32 new tokens each. The trace is served twice, cold
  (compiles included) and warm; every request must finish.
* reference — one prompt through the serving path (paged pool, chunked
  prefill, one paged decode step) with the fused kernels, with the jnp
  oracle in bf16, and with the oracle in float32 at full matmul
  precision, on the same chip and weights. For the prefill and the
  decode logits alike, the fused kernels may be no further from the
  float32 run than the bf16 oracle is; by the triangle inequality that
  holds |pallas - jnp| within twice the oracle's own bf16 error.
* train — five ``Trainer`` steps at ``seq_len`` 1024, batch 8, through the
  fused backward kernels; every loss must be finite.
* grads — the sandwich kernel in float32 at the MLP sites' widths
  (1024 -> 2048 and back, where every butterfly folds its in-lane stages
  onto the MXU) over four row tiles: its output and its four gradients
  against the float32 oracle at full matmul precision, within
  ``GRAD_BOUND`` of each tensor's largest entry.

``--four-chips`` runs only what exists across chips, each against one
device, at full width with the depth cut to ``FOUR_CHIP_LAYERS`` layers:
the engine on a 4-device ``("data",)`` mesh (float32 at full matmul
precision, tokens equal), the trainer on that mesh (losses within
``LOSS_BOUND``), and four replicas behind the router, replica ``i`` on
device ``i`` (tokens equal).

The kernel backend is pinned to ``pallas`` and checked after resolution,
so ``REPRO_KERNEL_BACKEND`` cannot move any phase to the jnp oracle or to
interpret mode. Without a TPU the script exits non-zero before any phase.
The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m-butterfly"
SEED = 0
CHUNK = 256                 # prefill chunk: prompts up to 1024 span 4
LOSS_BOUND = 1e-2           # bf16: |sharded - one device| / one device
GRAD_BOUND = 1e-5           # float32: max |kernel - oracle| / max |oracle|
FOUR_CHIP_LAYERS = 4


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(peak)


def pallas_config(cfg, **overrides):
    """``cfg`` with the kernel backend pinned to compiled Pallas."""
    bfly = dataclasses.replace(cfg.butterfly, backend="pallas")
    return cfg.with_(butterfly=bfly, **overrides)


def serve_trace(router, items, timeout: float = 900.0):
    """Submit every item, wait for all; returns (token lists, seconds)."""
    t0 = time.perf_counter()
    futs = [router.submit(it.request()) for it in items]
    results = [f.result(timeout=timeout) for f in futs]
    return [list(r.tokens) for r in results], time.perf_counter() - t0


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def phase_serve(cfg, params, device) -> list:
    from repro.launch.serve import build_replicas
    from repro.serve import Router, ServeEngine, trace

    spec = trace.TraceSpec(requests=8, seed=SEED, min_prompt=64,
                           max_prompt=1024, mix="bimodal", chunk=CHUNK,
                           max_new_tokens=32)
    items = trace.generate(spec, cfg.vocab_size)
    check(any(len(it.prompt) > CHUNK for it in items),
          "trace holds no prompt longer than a prefill chunk")
    engine, = build_replicas(1, cfg, params, lambda i, p, ctx: ServeEngine(
        cfg, p, slots=8, max_len=2048, pool="paged", prefill_chunk=CHUNK,
        seed=SEED, replica=i, context=ctx))
    check(engine.ctx.backend == "pallas",
          f"engine resolved backend {engine.ctx.backend!r}, not 'pallas'")
    check(engine.pool.kind == "paged", f"pool is {engine.pool.kind!r}")
    with Router([engine]) as router:
        cold, cold_s = serve_trace(router, items)
        cold_compiles = engine.compile_stats["compiles"]
        warm, warm_s = serve_trace(router, items)
    new = [len(t) for t in cold]
    check(new == [32] * len(items),
          f"not every request finished with 32 tokens: {new}")
    check(warm == cold, "the warm pass decoded other tokens than the cold")
    total = sum(new)
    print(f"serve: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
          f"backend=pallas | {len(items)}/{len(items)} requests finished "
          f"(prompts {min(len(i.prompt) for i in items)}-"
          f"{max(len(i.prompt) for i in items)} tokens, 32 new each) | "
          f"cold {total / cold_s:.1f} tok/s over {cold_s:.2f} s with "
          f"{cold_compiles} compiles | warm {total / warm_s:.1f} tok/s "
          f"over {warm_s:.2f} s, "
          f"{engine.compile_stats['compiles'] - cold_compiles} compiles | "
          f"peak_bytes_in_use={peak_bytes(device)}", flush=True)
    return items


def serving_logits(cfg, params, prompt, backend: str, token=None):
    """Logits through the serving path: the paged pool filled by chunked
    prefill, read at the prompt's last token, then one paged decode step
    of ``token`` (default: that prefill's argmax). Returns (prefill
    logits, decode logits, token) as float32 numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.context import ExecutionContext, use_execution
    from repro.models import lm
    from repro.serve.cache import make_pool

    n = len(prompt)
    pool = make_pool(cfg, 1, n + 1, kind="paged")
    pool.alloc_pages(0, n + 1)
    page_table = pool.gather_args()["page_table"]
    ctx = ExecutionContext(backend=backend)

    @jax.jit
    def chunk(params, caches, tokens, start, last):
        with use_execution(ctx):
            logits, _, caches = lm.prefill_chunk(cfg, params, tokens, caches,
                                                 start, last, page_table)
        return logits, caches

    @jax.jit
    def decode(params, caches, token):
        with use_execution(ctx):
            return lm.decode_step(cfg, params, token, caches,
                                  jnp.asarray([n], jnp.int32),
                                  page_table)[0]

    padded = np.zeros(-(-n // CHUNK) * CHUNK, np.int32)
    padded[:n] = prompt
    caches = pool.init()
    for start in range(0, n, CHUNK):
        logits, caches = chunk(
            params, caches, jnp.asarray(padded[None, start:start + CHUNK]),
            jnp.asarray([start], jnp.int32),
            jnp.asarray([min(CHUNK, n - start) - 1], jnp.int32))
    if token is None:
        token = int(jnp.argmax(logits[0]))
    step = decode(params, caches, jnp.asarray([token], jnp.int32))
    return (np.asarray(logits[0], np.float32),
            np.asarray(step[0], np.float32), token)


def phase_reference(cfg, params, prompt, device) -> None:
    import jax
    import numpy as np

    with jax.default_matmul_precision("highest"):
        exact = serving_logits(cfg.with_(compute_dtype="float32"), params,
                               prompt, "jnp")
    token = exact[2]
    fused = serving_logits(cfg, params, prompt, cfg.butterfly.backend, token)
    oracle = serving_logits(cfg, params, prompt, "jnp", token)
    parts, failed = [], []
    for i, name in enumerate(("prefill", "decode")):
        f, o, e = fused[i], oracle[i], exact[i]
        err_f = float(np.abs(f - e).max())
        err_o = float(np.abs(o - e).max())
        parts.append(
            f"{name}: max |pallas - f32| {err_f:.6g} vs max |jnp bf16 - "
            f"f32| {err_o:.6g}, max |pallas - jnp| "
            f"{float(np.abs(f - o).max()):.6g}, max |logit| "
            f"{float(np.abs(e).max()):.6g}, argmax pallas/jnp/f32 "
            f"{int(f.argmax())}/{int(o.argmax())}/{int(e.argmax())}")
        if not (np.isfinite(f).all() and err_f <= err_o):
            failed.append(f"{name} logits: pallas is {err_f:.6g} from "
                          f"float32, the bf16 oracle {err_o:.6g}")
    print(f"reference: a {len(prompt)}-token prompt through the paged pool "
          f"(chunked prefill, then one decode step) | "
          + " | ".join(parts)
          + f" | peak_bytes_in_use={peak_bytes(device)}", flush=True)
    check(not failed, "the fused kernels are less accurate than the bf16 "
                      "oracle: " + "; ".join(failed))


def train_losses(cfg, steps: int = 5, seq_len: int = 1024,
                 global_batch: int = 8):
    import numpy as np
    from repro.configs.base import TrainConfig
    from repro.train.trainer import Trainer

    tc = TrainConfig(total_steps=steps, warmup_steps=1, seed=SEED)
    result = Trainer(cfg, tc, seq_len=seq_len,
                     global_batch=global_batch).run(steps)
    check(result.execution.backend == "pallas",
          f"trainer resolved backend {result.execution.backend!r}, not "
          f"'pallas'")
    check(len(result.losses) == steps
          and bool(np.isfinite(result.losses).all()),
          f"losses not finite: {result.losses}")
    return result


def phase_train(cfg, device) -> None:
    result = train_losses(cfg)
    times = ", ".join(f"{t:.3f}" for t in result.step_times)
    print(f"train: {cfg.name} seq_len=1024 batch=8 backend=pallas | losses "
          f"{[round(x, 5) for x in result.losses]} | step seconds "
          f"[{times}] (first includes compile) | "
          f"peak_bytes_in_use={peak_bytes(device)}", flush=True)


def phase_grads(device) -> None:
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import butterfly as bf
    from repro.kernels import ref, tuning
    from repro.kernels.sandwich import one_hot_select, sandwich_matmul

    parts = []
    for n1, n2 in ((1024, 2048), (2048, 1024)):
        k1, k2 = round(math.log2(n1)), round(math.log2(n2))
        keys = jax.random.split(jax.random.PRNGKey(SEED), 4)
        args = (jax.random.normal(keys[0], (256, n1)),
                bf.random_weights(keys[1], n1),
                jax.random.normal(keys[2], (k2, k1)) / math.sqrt(k1),
                bf.random_weights(keys[3], n2))
        c = jax.random.normal(jax.random.PRNGKey(SEED + 1), (256, n2))
        rng = np.random.default_rng(SEED)
        sel_in = one_hot_select(np.sort(rng.choice(n1, k1, replace=False)),
                                n1)
        sel_out = one_hot_select(np.sort(rng.choice(n2, k2, replace=False)),
                                 n2).T
        s_in, s_out = math.sqrt(n1 / k1), math.sqrt(n2 / k2)

        def fused(x, b_in, core, b_out):
            return sandwich_matmul(x, b_in, sel_in, core, sel_out, b_out,
                                   scale_in=s_in, scale_out=s_out,
                                   block_b=64)

        def oracle(x, b_in, core, b_out):
            return ref.sandwich_ref(x, b_in, core, b_out, sel_in, sel_out,
                                    s_in, s_out)

        def out_and_grads(f):
            return (jax.jit(f)(*args),) + jax.jit(jax.grad(
                lambda *a: jnp.vdot(c, f(*a)), (0, 1, 2, 3)))(*args)

        got = out_and_grads(fused)
        with jax.default_matmul_precision("highest"):
            want = out_and_grads(oracle)
        errs = [float(jnp.abs(g - w).max() / jnp.abs(w).max())
                for g, w in zip(got, want)]
        folded = (tuning.folded_stages(n1), tuning.folded_stages(n2))
        parts.append(f"{n1}->{n2} folded={folded[0]}/{folded[1]} out, dx, "
                     f"db_in, dcore, db_out " + ", ".join(
                         f"{e:.2e}" for e in errs))
        check(max(errs) <= GRAD_BOUND,
              f"sandwich {n1}->{n2}: kernel and float32 oracle differ by "
              f"{max(errs):.3g} of the largest entry (bound {GRAD_BOUND})")
    print("grads: float32 sandwich kernel against the oracle, max |diff| / "
          "max |oracle| | " + " | ".join(parts)
          + f" | peak_bytes_in_use={peak_bytes(device)}", flush=True)


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def jitter_stage_weights(params):
    """Butterfly stage weights scaled by a seeded 1% jitter.

    The FJLT init makes the stages exact ±1/√2 Hadamard factors, so whole
    groups of output logits tie exactly and greedy argmax is decided by
    rounding; off that lattice the top-2 gaps are real, and token equality
    between two layouts is a sound test."""
    import jax

    def jitter(path, w):
        if getattr(path[-1], "key", None) not in ("b_in", "b_out"):
            return w
        key = jax.random.fold_in(jax.random.PRNGKey(SEED + 1), w.size % 7919)
        return w * (1 + 0.01 * jax.random.normal(key, w.shape, w.dtype))

    return jax.tree_util.tree_map_with_path(jitter, params)


def four_chip_engines(cfg, params, items):
    """Tokens from one device, the 4-device mesh, and 4 placed replicas."""
    from repro.kernels.context import ExecutionContext
    from repro.launch.serve import build_replicas
    from repro.serve import Router, ServeEngine

    def make(i, p, context):
        return ServeEngine(cfg, p, slots=4, max_len=1024, pool="paged",
                           prefill_chunk=CHUNK, seed=SEED, replica=i,
                           context=context)

    def run(engines):
        with Router(engines) as router:
            return serve_trace(router, items)[0]

    one, = build_replicas(1, cfg, params, make)
    want = run([one])
    mesh = ExecutionContext(mesh_shape=(4,))
    sharded, = build_replicas(1, cfg, params, make, mesh)
    check(sharded.ctx.mesh_layout() == "data=4",
          f"mesh layout {sharded.ctx.mesh_layout()!r}")
    check(sharded.ctx.backend == "pallas",
          f"sharded engine backend {sharded.ctx.backend!r}")
    got_mesh = run([sharded])
    replicas = build_replicas(4, cfg, params, make)
    got_replicas = run(replicas)
    return want, got_mesh, got_replicas, replicas


def first_difference(want, got) -> str:
    for r, (w, g) in enumerate(zip(want, got)):
        if w != g:
            i = next(i for i, (a, b) in enumerate(zip(w, g)) if a != b)
            return f"request {r} token {i}: {w} vs {g}"
    return "none"


def phase_four_chips(base_cfg) -> None:
    import jax
    import numpy as np
    from repro.serve import loader, trace

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, JAX sees "
                             f"{len(devices)}")
    # serving comparisons in float32 on weights off the FJLT tie lattice,
    # with XLA's matmuls at full float32 precision (the TPU default rounds
    # their operands to bf16, whose layout-dependent noise flips greedy
    # argmax on close logits); set process-wide, as the router's driver
    # thread traces the steps
    cfg = pallas_config(base_cfg, n_layers=FOUR_CHIP_LAYERS,
                        compute_dtype="float32")
    params = jitter_stage_weights(loader.load_for_serving(cfg, seed=SEED)[1])
    items = trace.generate(trace.TraceSpec(
        requests=8, seed=SEED, min_prompt=64, max_prompt=512, mix="bimodal",
        chunk=CHUNK, max_new_tokens=16), cfg.vocab_size)
    precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        want, got_mesh, got_replicas, replicas = four_chip_engines(
            cfg, params, items)
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    check(got_mesh == want, f"the 4-device mesh engine decoded other tokens "
                            f"than one device (first difference: "
                            f"{first_difference(want, got_mesh)})")
    print(f"four-chips serve mesh: {cfg.name} layers={cfg.n_layers} float32 "
          f"(highest matmul precision), ExecutionContext(mesh_shape=(4,)) vs"
          f" one device: "
          f"{len(items)} requests, tokens equal", flush=True)
    for i, eng in enumerate(replicas):
        devs = sorted(str(d) for d in eng.devices())
        print(f"four-chips replica[{i}].devices() = {devs}", flush=True)
        check(eng.devices() == {devices[i]},
              f"replica {i} holds arrays on {devs}, not {devices[i]}")
    check(got_replicas == want, f"4 placed replicas decoded other tokens "
                                f"than one replica (first difference: "
                                f"{first_difference(want, got_replicas)})")
    print(f"four-chips router: 4 replicas, replica i on device i, "
          f"{len(items)} requests, tokens equal to one replica's",
          flush=True)

    tcfg = pallas_config(base_cfg, n_layers=FOUR_CHIP_LAYERS)
    one = train_losses(tcfg).losses
    mcfg = tcfg.with_(butterfly=dataclasses.replace(tcfg.butterfly,
                                                    mesh_shape=(4,)))
    sharded = train_losses(mcfg)
    check(sharded.execution.mesh_layout == "data=4",
          f"trainer mesh {sharded.execution.mesh_layout!r}")
    rel = np.abs(np.asarray(sharded.losses) - one) / np.abs(one)
    print(f"four-chips train: {tcfg.name} layers={tcfg.n_layers} bf16, "
          f"mesh_shape=(4,) losses {[round(x, 5) for x in sharded.losses]} "
          f"vs one device {[round(x, 5) for x in one]}: max relative diff "
          f"{rel.max():.3g} (bound {LOSS_BOUND})", flush=True)
    check(rel.max() <= LOSS_BOUND, f"sharded trainer losses differ by "
                                   f"{rel.max():.3g} > {LOSS_BOUND}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the multi-chip paths against one device, "
                         "and nothing else (needs 4 TPU chips)")
    args = ap.parse_args()

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's first device is "
              f"{device.platform!r} ({device.device_kind}); this check runs "
              f"only on a TPU", file=sys.stderr)
        return 1
    try:
        from repro.configs import registry
        from repro.launch.compile_cache import enable_compile_cache
        from repro.serve import loader
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"(src/repro): {e}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count(event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and \
                name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count)
    print(f"chip_smoke: {len(jax.devices())} x {device.device_kind}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    base = registry.get(ARCH)
    try:
        if args.four_chips:
            phase_four_chips(base)
        else:
            cfg = pallas_config(base)
            params = loader.load_for_serving(cfg, seed=SEED)[1]
            items = phase_serve(cfg, params, device)
            phase_reference(cfg, params, items[1].prompt, device)
            phase_train(cfg, device)
            phase_grads(device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {cache_events['cache_hits']} hits, "
          f"{cache_events['cache_misses']} misses in {cache}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
