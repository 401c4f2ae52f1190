"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
program's own names say: the device programs, and the engine's or
trainer's phases on the device trace's clock.

* ``modules``: each device program's executions on the ``XLA Modules``
  line (``jit_serve_decode``, ``jit_serve_chunk_prefill``,
  ``jit_train_step`` …, the run-specific ``(id)`` cut off): count, total
  seconds and median milliseconds.
* ``phases``: the host events the program annotates with
  ``repro.obs.Tracer.phase`` (names starting ``serve.`` or ``train.``):
  count and total seconds of each.
* the phases' window, from the first phase's start to the last one's
  end: ``window_s``, the device's ``busy_s`` inside it (union of its
  ``XLA Ops`` intervals, clipped to the window), ``idle_s``, and
  ``idle_by_phase``, the idle seconds split by the innermost phase
  covering them, with ``"no phase"`` for idle time between phases.
  Device figures are averaged over the devices that ran.

``readings`` turns the reduction into the per-layer numbers it gives:
``decode_step_ms.serve`` and ``chunk_step_ms.serve`` (median device
milliseconds of the decode and chunked-prefill programs),
``idle_in_ticks.serve`` (idle share of the engine's window),
``idle_host_work.serve`` (the idle share under the engine's own host
phases, every ``serve.*`` phase but ``serve.readback``, where the host
waits for the device) and ``idle_in_steps.train`` (idle share of the
trainer's window). A trace without the programs or phases gives none of
them.

    python3 chipbench/spans.py <trace.xplane.pb[.gz]>

prints the reduction and its readings as one JSON object.
"""

from __future__ import annotations

import gzip
import json
import statistics
import sys
from typing import Dict, List, Tuple

if __package__ in (None, ""):              # run as a script
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench.xplane import (DEVICE_PREFIX, HOST_PLANE, MODULES_LINE,
                              OPS_LINE, _events, _union)

PHASE_PREFIXES = ("serve.", "train.")
NO_PHASE = "no phase"
READBACK = "serve.readback"
PROGRAMS = {"decode_step_ms.serve": "jit_serve_decode",
            "chunk_step_ms.serve": "jit_serve_chunk_prefill"}


def program_name(event_name: str) -> str:
    """``jit_serve_decode(1409094998828362496)`` -> ``jit_serve_decode``."""
    return event_name.split("(", 1)[0]


def phase_events(planes) -> List[Tuple[int, int, str]]:
    """The program's phases on the host plane: ``(start, end, name)`` in
    nanoseconds, sorted by start."""
    out = []
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith(PHASE_PREFIXES):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name))
    out.sort()
    return out


def segments(phases: List[Tuple[int, int, str]], w0: int, w1: int
             ) -> List[Tuple[int, int, str]]:
    """``[w0, w1]`` cut into stretches, each named after the innermost
    phase covering it (the one that started last), or ``NO_PHASE``."""
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(phases)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(phases)])
    active: Dict[int, int] = {}
    out, t = [], w0
    for x, opening, i in edges:
        x = min(max(x, w0), w1)
        if x > t:
            name = (phases[max(active, key=lambda j: (active[j], j))][2]
                    if active else NO_PHASE)
            out.append((t, x, name))
            t = x
        if opening:
            active[i] = phases[i][0]
        else:
            active.pop(i, None)
    if t < w1:
        out.append((t, w1, NO_PHASE))
    return out


def _idle_by_name(busy: List[Tuple[int, int]],
                  segs: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of each segment's name not covered by ``busy`` (a
    sorted union inside the segments' span)."""
    out: Dict[str, int] = {}
    j = 0
    for a, b, name in segs:
        idle = b - a
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            idle -= min(busy[k][1], b) - max(busy[k][0], a)
            k += 1
        if idle:
            out[name] = out.get(name, 0) + idle
    return out


def reduce_planes(planes) -> Dict:
    planes = list(planes)
    mods: Dict[str, List[int]] = {}
    devices = []
    for p in planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        for name, _, d in _events(p, MODULES_LINE):
            mods.setdefault(program_name(name), []).append(d)
        iv = [(s, s + d) for _, s, d in _events(p, OPS_LINE)]
        if not iv:
            iv = [(s, s + d) for _, s, d in _events(p, MODULES_LINE)]
        if iv:
            devices.append(_union(iv))
    phases = phase_events(planes)
    counts: Dict[str, List[float]] = {}
    for s, e, name in phases:
        c = counts.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    out = {"modules": {k: {"count": len(v), "seconds": sum(v) * 1e-9,
                           "median_ms": statistics.median(v) * 1e-6}
                       for k, v in sorted(mods.items())},
           "phases": {k: {"count": c, "seconds": t}
                      for k, (c, t) in sorted(counts.items())},
           "devices": len(devices), "window_s": 0.0, "busy_s": 0.0,
           "idle_s": 0.0, "idle_by_phase": {}}
    if not phases or not devices:
        return out
    w0, w1 = phases[0][0], max(e for _, e, _ in phases)
    segs = segments(phases, w0, w1)
    busy_ns, idle_ns = 0, {}
    for u in devices:
        clipped = [(max(s, w0), min(e, w1)) for s, e in u
                   if e > w0 and s < w1]
        busy_ns += sum(e - s for s, e in clipped)
        for k, v in _idle_by_name(clipped, segs).items():
            idle_ns[k] = idle_ns.get(k, 0) + v
    n = len(devices)
    idle = sorted(idle_ns.items(), key=lambda kv: -kv[1])
    out.update(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns / n * 1e-9,
               idle_s=((w1 - w0) - busy_ns / n) * 1e-9,
               idle_by_phase={k: v / n * 1e-9 for k, v in idle})
    return out


def readings(sp: Dict) -> Dict[str, float]:
    """The per-layer numbers a reduction gives (module docstring)."""
    got = {k: sp["modules"][m]["median_ms"] for k, m in PROGRAMS.items()
           if m in sp["modules"]}
    w = sp["window_s"]
    if w <= 0:
        return got
    if any(k.startswith("serve.") for k in sp["phases"]):
        got["idle_in_ticks.serve"] = 100.0 * sp["idle_s"] / w
        host = sum(v for k, v in sp["idle_by_phase"].items()
                   if k.startswith("serve.") and k != READBACK)
        got["idle_host_work.serve"] = 100.0 * host / w
    if any(k.startswith("train.") for k in sp["phases"]):
        got["idle_in_steps.train"] = 100.0 * sp["idle_s"] / w
    return got


def read_planes(path: str):
    """The planes of a ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read()).planes


def reduce_file(path: str) -> Dict:
    return reduce_planes(read_planes(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 chipbench/spans.py <trace.xplane.pb[.gz]>",
              file=sys.stderr)
        return 2
    sp = reduce_file(argv[0])
    sp["readings"] = readings(sp)
    print(json.dumps(sp))
    return 0


if __name__ == "__main__":
    sys.exit(main())
