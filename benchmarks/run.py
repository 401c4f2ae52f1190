"""Benchmark runner: one section per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines (assignment format). Roofline
numbers come from the dry-run artifacts (``python -m repro.launch.dryrun``)
— summarized here if present.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import (bench_autoencoder, bench_backward,  # noqa: E402
                        bench_kernels, bench_lm_butterfly, bench_nonlinear,
                        bench_param_counts, bench_serving, bench_sketch,
                        bench_speed, bench_theorem1, bench_two_phase,
                        common)


def summarize_dryrun(out_dir: str = "experiments/dryrun") -> None:
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if r.get("status") == "skipped":
            print(f"roofline/{r['arch']}__{r['shape']}__{r['mesh']},0.00,"
                  f"status=skipped;reason={r['reason']}")
            continue
        print(f"roofline/{r['arch']}__{r['shape']}__{r['mesh']},0.00,"
              f"t_compute={r['t_compute']:.4f};t_memory={r['t_memory']:.4f};"
              f"t_collective={r['t_collective']:.4f};"
              f"dominant={r['dominant']};util={r['flops_utilization']:.3f};"
              f"fit={r['hbm_fit']}")


def write_json(mode: str) -> str:
    """Dump every emitted row as BENCH_<mode>.json (the CI perf artifact)."""
    import jax

    path = f"BENCH_{mode}.json"
    payload = {
        "mode": mode,
        "jax_backend": jax.default_backend(),
        "rows": common.ROWS,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main() -> None:
    import argparse

    parser = argparse.ArgumentParser(
        description="paper benchmark runner (CSV on stdout + BENCH_*.json)")
    # --quick (the CI gate) is an alias of the original --fast; argparse
    # rejects typos instead of silently running the full multi-hour sweep
    parser.add_argument("--quick", "--fast", dest="quick",
                        action="store_true",
                        help="reduced steps/sizes (the per-PR CI gate)")
    fast = parser.parse_args().quick
    print("name,us_per_call,derived")
    bench_param_counts.run()
    bench_theorem1.run()
    bench_kernels.run()
    bench_speed.run()
    bench_backward.run(ns=bench_backward.NS, batch=16 if fast else 64,
                       iters=5 if fast else None)
    bench_nonlinear.run(steps=120 if fast else 300)
    if fast:
        bench_autoencoder.run(train_steps=60)
        bench_two_phase.run(steps1=60, steps2=40)
        bench_sketch.run(steps=30)
        bench_lm_butterfly.run(steps=15)
        bench_serving.run(requests=24, max_new=8)
    else:
        bench_autoencoder.run()
        bench_two_phase.run()
        bench_sketch.run()
        bench_sketch.run_ell_sweep()
        bench_lm_butterfly.run()
        bench_serving.run()
    summarize_dryrun()
    path = write_json("quick" if fast else "full")
    print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
