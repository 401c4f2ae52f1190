"""Roofline-term extraction from AOT-compiled artifacts.

Per (arch × shape × mesh) cell the dry-run produces a compiled executable;
this module derives the three roofline terms (seconds, per device):

    compute    = per_device_HLO_FLOPs / peak FLOP/s
    memory     = per_device_HLO_bytes / HBM bandwidth
    collective = per_device_collective_bytes / ICI bandwidth per link
                 (+ DCN-crossing collectives on the `pod` axis at DCN_BW,
                  reported separately and included in the term)

``cost_analysis()`` returns **post-SPMD per-device** numbers (verified in
tests). Collective bytes are NOT in cost_analysis — they are parsed from the
compiled HLO text: we sum output-shape bytes of every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute op, classified
by whether the replica group spans the ``pod`` axis.

Hardware constants live in one table, :data:`DEVICE_SPECS`, keyed by the
``device_kind`` JAX reports; :func:`device_spec` refuses a kind that is not
in it. The dry-run models a v5e pod (:data:`V5E`); DCN between pods is
modeled at 25 GB/s/host-link.
"""

from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class DeviceSpec:
    """Per-chip peaks and on-chip memory of one TPU generation."""

    kind: str                  # jax.devices()[i].device_kind
    peak_flops: float          # bf16 FLOP/s
    hbm_bw: float              # HBM bytes/s
    hbm_bytes: int             # HBM capacity
    ici_bw: float              # interconnect bytes/s per link
    vmem_bytes: int            # VMEM per TensorCore
    scoped_vmem_bytes: int     # compiler's default scoped-VMEM limit
    source: str


DEVICE_SPECS: Dict[str, DeviceSpec] = {
    "TPU v5 lite": DeviceSpec(
        kind="TPU v5 lite", peak_flops=197e12, hbm_bw=819e9,
        hbm_bytes=16 * 10 ** 9,
        ici_bw=1600e9 / 8 / 4,     # 1,600 Gbit/s per chip over 4 links
        vmem_bytes=128 * 2 ** 20, scoped_vmem_bytes=16 * 2 ** 20,
        source=("Google Cloud documentation, 'TPU v5e' (peak bf16, HBM "
                "capacity and bandwidth, interconnect); VMEM from the chip "
                "table of jax._src.pallas.mosaic.tpu_info (jax 0.9.0); "
                "scoped-VMEM default from the TPU compiler's limit "
                "(libtpu 0.0.34)")),
}

V5E = DEVICE_SPECS["TPU v5 lite"]
DCN_BW = 25e9              # bytes/s per pod uplink (modeled)


def device_spec(kind: str) -> DeviceSpec:
    """The table row for one ``device_kind``; an unknown TPU is an error,
    never a default."""
    try:
        return DEVICE_SPECS[kind]
    except KeyError:
        raise ValueError(
            f"no peak/VMEM entry for device_kind {kind!r}; add a row with "
            f"its source to repro.launch.roofline.DEVICE_SPECS "
            f"(known: {sorted(DEVICE_SPECS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"(?P<outshape>[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape literal like ``bf16[8,128,2048]{2,1,0}``.

    Tuple shapes (e.g. all-reduce of several tensors) are handled by the
    caller summing every embedded shape.
    """
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        d = m.group("dtype")
        if d not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for x in dims.split(","):
                n *= int(x)
        total += n * _DTYPE_BYTES[d]
    return total


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    bytes_by_op: Dict[str, int] = field(default_factory=dict)
    ici_bytes: int = 0
    dcn_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return self.ici_bytes + self.dcn_bytes


def parse_collectives(hlo_text: str, pod_boundary: int = 0
                      ) -> CollectiveStats:
    """Sum collective payload bytes from post-SPMD HLO.

    ``pod_boundary``: number of devices per pod; a collective whose replica
    group spans device ids in different pods is classified as DCN traffic.
    Payload accounting is per-device: the op's (per-shard) output bytes.
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        nbytes = shape_bytes(m.group("outshape"))
        stats.counts[op] = stats.counts.get(op, 0) + 1
        stats.bytes_by_op[op] = stats.bytes_by_op.get(op, 0) + nbytes
        crosses_pod = False
        if pod_boundary:
            g = re.search(r"replica_groups=\[[^\]]*\]<=\[([0-9,]+)\]", line)
            if g:
                # iota-style groups: crosses pods iff a group dim spans
                # beyond one pod worth of devices
                rg = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
                if rg:
                    group_size = int(rg.group(2))
                    n_groups = int(rg.group(1))
                    # contiguous iota grouping: group spans pods when
                    # group_size > pod_boundary OR stride layout crosses
                    crosses_pod = group_size * _group_stride(
                        line, n_groups, group_size) > pod_boundary
        if crosses_pod:
            stats.dcn_bytes += nbytes
        else:
            stats.ici_bytes += nbytes
    return stats


def _group_stride(line: str, n_groups: int, group_size: int) -> int:
    """Detect transposed iota groups ([G,S]<=[S,G]T(1,0) ⇒ stride G)."""
    if re.search(r"<=\[[0-9,]+\]T\(", line):
        return n_groups
    return 1


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    collective: CollectiveStats
    # memory_analysis (per device)
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    # model-level accounting
    model_flops: float = 0.0       # 6·N_active·D (per device share)
    params_total: int = 0
    params_active: int = 0
    tokens: int = 0

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / V5E.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / V5E.hbm_bw

    @property
    def t_collective(self) -> float:
        return (self.collective.ici_bytes / V5E.ici_bw
                + self.collective.dcn_bytes / DCN_BW)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent on *useful* compute if perfectly
        overlapped: model_flops_time / max(term)."""
        t_model = self.model_flops / V5E.peak_flops
        b = self.bound_time
        return t_model / b if b > 0 else 0.0

    @property
    def flops_utilization(self) -> float:
        """MODEL_FLOPS / HLO_FLOPS — how much compiled compute is useful
        (catches remat / causal-overcompute waste)."""
        return (self.model_flops / self.flops_per_device
                if self.flops_per_device else 0.0)

    @property
    def hbm_fit(self) -> bool:
        per_dev = (self.argument_bytes + self.output_bytes
                   + self.temp_bytes - self.alias_bytes)
        return per_dev <= V5E.hbm_bytes

    def to_dict(self) -> Dict:
        d = {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_devices": self.n_devices,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_ici_bytes": self.collective.ici_bytes,
            "collective_dcn_bytes": self.collective.dcn_bytes,
            "collective_counts": self.collective.counts,
            "collective_bytes_by_op": self.collective.bytes_by_op,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "params_total": self.params_total,
            "params_active": self.params_active,
            "tokens": self.tokens,
            "flops_utilization": self.flops_utilization,
            "roofline_fraction": self.roofline_fraction,
            "hbm_fit": self.hbm_fit,
        }
        return d


def build_report(arch: str, shape: str, mesh_name: str, n_devices: int,
                 compiled, *, pod_boundary: int, model_flops: float,
                 params_total: int, params_active: int, tokens: int
                 ) -> RooflineReport:
    from repro.launch import hlo_analysis as ha
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    # loop-aware analysis: cost_analysis() counts while-loop bodies once
    # (verified in tests) — our layer stacks are scans, so that is useless.
    cost = ha.analyze(text, pod_boundary=pod_boundary)
    stats = CollectiveStats(
        counts={k: int(v) for k, v in cost.collective_counts.items()},
        bytes_by_op={k: int(v) for k, v in cost.collective_bytes.items()},
        ici_bytes=int(cost.collective_ici),
        dcn_bytes=int(cost.collective_dcn))
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=float(cost.flops),
        bytes_per_device=float(cost.hbm_bytes),
        collective=stats,
        argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
        temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
        alias_bytes=int(getattr(ma, "alias_size_in_bytes", 0)),
        model_flops=model_flops, params_total=params_total,
        params_active=params_active, tokens=tokens)
