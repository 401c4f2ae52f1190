"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device busy
time, per-operation device time and the longest idle gaps.

Busy time is the union of the intervals in which an operation runs on a
device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged
over the devices that ran anything. An idle gap is a stretch between two
busy intervals of one device; it is labelled with the host activity that
overlaps it most (the longest host event that covers part of the gap, on
any host thread), or ``"no host event"``.
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for ev in line.events:
                yield ev.name, int(ev.start_ns), int(ev.duration_ns)


def _host_events(planes) -> List[Tuple[int, int, str]]:
    out = []
    for p in planes:
        if p.name != HOST_PLANE:
            continue
        for line in p.lines:
            for ev in line.events:
                d = int(ev.duration_ns)
                if d > 0:
                    s = int(ev.start_ns)
                    out.append((s, s + d, ev.name))
    out.sort()
    return out


def _label(gap: Tuple[int, int], host: List[Tuple[int, int, str]]) -> str:
    best, best_len = "no host event", 0
    s, e = gap
    for hs, he, name in host:
        if hs >= e:
            break
        ov = min(he, e) - max(hs, s)
        if ov > best_len:
            best, best_len = name, ov
    return best


def short_name(name: str) -> str:
    """An XLA op's event name, which is its whole HLO instruction, cut to
    its name, opcode and (for one array) result type:
    ``%fusion.3 = bf16[64,576]{1,0} fusion(...)`` gives
    ``%fusion.3 fusion bf16[64,576]``."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    if rhs.startswith("("):                # a tuple: skip its element types
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        kind, rest = "tuple", rhs[i + 1:]
    else:
        kind, _, rest = rhs.partition(" ")
        kind = kind.split("{")[0]
    opcode = rest.strip().split("(")[0]
    return f"{lhs} {opcode} {kind}"


def reduce_planes(planes, window_s: float, top: int = 10) -> Dict:
    """``busy_s`` (mean over devices that ran something), ``ops``
    (device seconds per operation name, summed over devices), and the
    ``top`` longest ``idle_gaps`` as ``[label, seconds]``."""
    planes = list(planes)
    busy, ops = [], collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for p in planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        iv = []
        for name, s, d in _events(p, OPS_LINE):
            ops[name] += d
            iv.append((s, s + d))
        if not iv:
            iv = [(s, s + d) for _, s, d in _events(p, MODULES_LINE)]
        if not iv:
            continue
        u = _union(iv)
        busy.append(sum(e - s for s, e in u))
        gaps.extend((a[1], b[0]) for a, b in zip(u, u[1:]) if b[0] > a[1])
    if not busy:
        return {"busy_s": 0.0, "window_s": window_s, "devices": 0,
                "ops": {}, "idle_gaps": [], "device_ops": []}
    gaps.sort(key=lambda g: g[0] - g[1])
    host = _host_events(planes)
    idle = [[_label(g, host), (g[1] - g[0]) * 1e-9] for g in gaps[:top]]
    ops_s = {k: v * 1e-9 for k, v in ops.items()}
    return {"busy_s": sum(busy) / len(busy) * 1e-9, "window_s": window_s,
            "devices": len(busy), "ops": ops_s, "idle_gaps": idle,
            "device_ops": [[short_name(k), v] for k, v in
                           sorted(ops_s.items(), key=lambda kv: -kv[1])
                           [:top]]}


def reduce_file(path: str, window_s: float, top: int = 10) -> Dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s, top)


def op_seconds(trace: Optional[Dict], patterns) -> Optional[float]:
    """Device seconds of the operations whose name contains any of
    ``patterns``; ``None`` when none ran."""
    if not trace:
        return None
    t = sum(v for k, v in trace["ops"].items()
            if any(p in k for p in patterns))
    return t if t > 0 else None
