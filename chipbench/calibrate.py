#!/usr/bin/env python3
"""Readings that set a cell's limits, and the knee of its load, in one
process per call (the benchmark's own runs never run this).

    python chipbench/calibrate.py limits --workload <w> --seeds 1,2,3 \
        --seconds <s>
    python chipbench/calibrate.py knee --workload <w> --rates 2,4,6 \
        --seconds <s>

``limits`` builds the cell once and, for each seed, loads that seed's
weights, offers that seed's traffic for ``--seconds`` at the cell's own
rate, drains it, and compares the sampled requests with the reference:
the program's widest and mean served-token gaps, and the control's (the
reference computed with float8 operands, reading at the same positions
the gap of the token it puts first). Both go through the verdict of a
benchmark run against the cell's limits file: ``correct`` for the
program, ``control_correct`` for the control, which has to be false.
``train-limits`` does the same for the training cell, with the
half-batch fault beside the control. One JSON line per seed.

``knee`` offers each rate in turn, for ``--seconds`` each after the one
set-up, and prints what the window offered and finished, and how the
backlog grew: the knee is the highest rate whose backlog does not grow.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def _counters(engines):
    """The engines' decode and chunk counters, summed."""
    keys = ("decode_time_s", "decode_steps", "chunk_ticks")
    return {k: sum(float(getattr(e.metrics, k)) for e in engines)
            for k in keys}


def _reload(engines, params):
    import jax

    for e in engines:
        dev = next(iter(e.devices()))
        e.set_params(jax.device_put(params, dev))


def limits(cell, seeds, seconds: float, out=sys.stdout, backend=None,
           limit_values=None):
    import jax
    from chipbench import limits as limits_file
    from chipbench import serve_driver as sd
    from chipbench import weights
    from repro.models import lm
    from repro.runtime import pytree as pt

    compiles = sd.CompileLog()
    cfg, params, engines, submit, front = sd.build(cell, seeds[0], backend)
    sd.warm(engines, submit, cfg, cell.traffic, seeds[0])
    shapes = pt.abstract_params(lm.model_specs(cfg))
    ref = cell.traffic["reference"]
    lim = (limits_file.load(cell.name) if limit_values is None
           else limit_values)
    rows = []
    for seed in seeds:
        params = weights.make(shapes, seed, device=jax.devices()[0])
        _reload(engines, params)
        w = sd.measure(cell, engines, submit, cfg, seed, seconds, False,
                       compiles, float(cell.traffic["drain_s"]))
        picked = sd.sample(w, seed, ref["max_requests"])
        unfinished = sum(not r["ok"] for r in w.requests)
        cmp = sd.compare(cell, params, picked, quant="fp8")
        row = {"seed": seed, "unfinished": unfinished}
        row.update(cmp)
        row["correct"], row["checks"] = sd.verdict(
            cmp, unfinished, w.compiles, lim, ref["min_tokens"])
        row["control_correct"], row["control_checks"] = sd.verdict(
            sd.control_readings(cmp), unfinished, w.compiles, lim,
            ref["min_tokens"])
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
        for e in engines:
            e.reset_metrics()
    front.close()
    return rows


def knee(cell, rates, seconds: float, out=sys.stdout, backend=None):
    from chipbench import gen
    from chipbench import serve_driver as sd
    from repro.serve import Request

    from chipbench import stats

    cfg, params, engines, submit, front = sd.build(cell, 1, backend)
    sd.warm(engines, submit, cfg, cell.traffic, 1)
    rows, rid = [], 1 << 20
    for rate in rates:
        items = gen.generate(cell.traffic, int(rate * 1000), seconds,
                             cfg.vocab_size, rate=rate)
        queued = []

        def between(now, t0=time.monotonic()):
            if len(queued) < int((now - t0) / (seconds / 4)):
                queued.append(sum(e.queued() for e in engines))

        t0 = time.monotonic()
        rids = list(range(rid, rid + len(items)))
        rid += len(items)
        it_rid = dict(zip(map(id, items), rids))
        futs, late = gen.replay(submit, items, t0, lambda it: Request(
            prompt=it.prompt, max_new_tokens=it.max_new_tokens,
            rid=it_rid[id(it)]), between=between)
        while time.monotonic() < t0 + seconds:
            time.sleep(0.01)
        queued.append(sum(e.queued() for e in engines))
        occupied = sum(e.occupied_slots() for e in engines)
        ttft, toks, done = [], 0, 0
        for it, f in zip(items, futs):
            if f is not None and f.done() and f.exception() is None:
                m = f.result().metrics
                done += 1
                toks += sd._tokens_in(m.first_token_t, m.finish_t,
                                      m.new_tokens, t0, t0 + seconds)
        for e in engines:
            for s in list(e._slots) + list(e._queue):
                if s is not None and s.rid in rids:
                    m = e.metrics.request(s.rid)
                    if m is not None and m.first_token_t:
                        toks += sd._tokens_in(m.first_token_t,
                                              time.monotonic(),
                                              m.new_tokens, t0,
                                              t0 + seconds)
        for f in futs:
            if f is not None and f.done() and f.exception() is None:
                m = f.result().metrics
                ttft.append(m.first_token_t - m.submit_t)
        c = _counters(engines)
        for r in rids:
            front.cancel(r)
        while any(e.has_work() for e in engines):
            time.sleep(0.05)
        row = {"rate": rate, "offered": len(items),
               "finished_in_window": done, "queued_quarterly": queued,
               "occupied_at_close": occupied,
               "ttft_p95_ms_finished": (stats.percentile(ttft, 0.95) * 1e3
                                        if ttft else None),
               "gen_tok_s": toks / seconds, "max_late_s": max(late),
               "decode_step_ms": 1e3 * c["decode_time_s"]
               / max(1, c["decode_steps"]),
               "chunk_ticks_per_s": c["chunk_ticks"] / seconds,
               "decode_steps_per_s": c["decode_steps"] / seconds}
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
        for e in engines:
            e.reset_metrics()
    front.close()
    return rows


def train_limits(cell, seeds, out=sys.stdout, backend=None,
                 limit_values=None):
    """Per seed: the program's first three steps, and the reference's
    readings for the float32 reference, the float8 control and the
    half-batch fault put in the program's place, each through the verdict
    of a benchmark run (``<name>_correct``; the control's and the fault's
    have to be false)."""
    import gc
    from chipbench import limits as limits_file
    from chipbench import train_driver as td

    lim = (limits_file.load(cell.name) if limit_values is None
           else limit_values)

    rows = []
    for seed in seeds:
        cfg, trainer, data, w0 = td.build(cell, seed, backend)
        prog = td.first_steps(trainer, data, w0)
        trainer.params = trainer.opt_state = None
        del trainer
        gc.collect()
        t = time.monotonic()
        ref = td.reference_readings(cell, w0, data, 3)
        ref_s = time.monotonic() - t
        row = {"seed": seed, "reference_s": ref_s,
               "losses": prog["losses"], "ref_losses": ref["losses"],
               "program": td.compare(prog, ref)}
        row["correct"] = td.verdict(row["program"], 0, lim)[0]
        half = cell.traffic["trainer"]["batch"] // 2
        for name, kw in (("control", {"quant": "fp8"}),
                         ("half_batch", {"rows": half})):
            row[name] = td.compare(td.reference_readings(
                cell, w0, data, 3, **kw), ref)
            row[name + "_correct"] = td.verdict(row[name], 0, lim)[0]
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
        del w0
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("limits", "knee", "train-limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import manifest, run
    from repro.launch.compile_cache import enable_compile_cache

    cell = manifest.load_cell(args.workload)
    run.check_devices(cell.chips)
    enable_compile_cache()
    if args.mode == "limits":
        limits(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    elif args.mode == "train-limits":
        train_limits(cell, [int(s) for s in args.seeds.split(",")])
    else:
        knee(cell, [float(r) for r in args.rates.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
