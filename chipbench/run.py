#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` (see ``chipbench/manifest.py``). With
``--trace 0`` the result reports the cell's end-to-end metrics; with
``--trace 1`` a profiler window inside the measured one, and the
per-layer metrics read from it and from the request stamps. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, (traced) ``breakdown``,
``setup_phases`` (the seconds of each part of ``setup_s``),
``after_window`` (the seconds of the drain and of the reference),
``compile_log`` (what reached JAX's compiler in set-up, in the window and
in the reference), ``host`` (this process's CPU seconds over the
window and, serving, the engines' ticks in it), and last ``checks``: each
number compared for ``correct`` beside its limit, which also close
standard error.

Without a TPU, with fewer chips than the cell asks for, or without the
program next to the benchmark, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


class NoChip(RuntimeError):
    pass


def check_devices(chips: int):
    """The devices a cell runs on: TPUs only, at least ``chips`` of them,
    of a kind the peak table knows."""
    import jax
    from chipbench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r} "
                     f"({devs[0].device_kind}); this benchmark runs only "
                     f"on TPUs")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    peaks.peaks_for(devs[0].device_kind)
    return devs[:chips]


def layer_metrics(cell, out: dict, seconds: float) -> dict:
    """The cell's per-layer metrics from its readers; a reader that finds
    nothing to read returns ``None`` and its metric is left out."""
    from chipbench import manifest

    got = {}
    for m in cell.per_layer:
        v = manifest.layer_metric(m["name"]).read(out)
        if v is not None:
            got[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return got


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             devices=None, backend=None, t_start: float = None,
             limit_values: dict = None, start_s: float = None) -> dict:
    """Everything after the look for a chip: drive the cell, compare, and
    build the result line (as a dict). ``limit_values`` replaces the
    cell's limits file (tests at sizes the file is not for); ``start_s``
    is the set-up's first part, process start to the chip found."""
    from chipbench import limits, manifest, peaks

    t_start = T_START if t_start is None else t_start
    lim = (limits.load(cell.name) if limit_values is None
           else limit_values)
    driver = manifest.driver(cell.traffic["driver"])
    out = driver.run(cell, seed, seconds, trace, backend=backend,
                     limits=lim, t_start=t_start)
    devs = devices if devices is not None else out["devices"]
    out["peaks"] = (peaks.peaks_for(devs[0].device_kind)
                    if devs[0].platform == "tpu" else None)
    out["cell"] = cell
    out["seconds"] = seconds
    if trace:
        metrics = layer_metrics(cell, out, seconds)
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    tr = out.get("trace")
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    phases = {} if start_s is None else {"start_s": start_s}
    phases.update(out["setup_phases"])
    result["setup_phases"] = phases
    for k in ("after_window", "compile_log", "host"):
        result[k] = out[k]
    result["checks"] = {k: {"value": float(v), "limit": float(l)}
                        for k, (v, l) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from chipbench import manifest
        cell = manifest.load_cell(args.workload)
        from repro.launch.compile_cache import enable_compile_cache
    except (ImportError, OSError, KeyError) as e:
        print(f"chipbench: cannot load the cell or the program: {e!r}",
              file=sys.stderr)
        return 2
    try:
        devices = check_devices(cell.chips)
    except (NoChip, ValueError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, start_s=time.monotonic() - T_START)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
