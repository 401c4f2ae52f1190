"""Analytic VMEM/roofline autotuner for the fused Pallas kernels.

Every kernel in this package used to hard-code ``block_b = 256`` (and flash
``block_q = block_kv = 128``) regardless of n, dtype, or direction. That is
exactly the flat, hardware-unaware choice Pixelated Butterfly warns turns
theoretical sparsity into wall-clock slowdowns: at n = 8192 the segmented
backward keeps ~2·⌈√p⌉ activation tiles live, so a 256-row tile would need
>80 MB of VMEM — an order of magnitude over budget — while at n = 256 a
256-row tile underutilizes the VPU lanes.

This module picks ``block_b`` (batch-tile rows) and ``segment`` (backward
checkpoint segment length, see :mod:`repro.kernels.butterfly`) per
``(kernel, n, dtype, direction)`` from an analytic VMEM-footprint model plus
the chip's row of :data:`repro.launch.roofline.DEVICE_SPECS`:

* footprint model — double-buffered input/output tiles, the float32 blocks
  every grid step revisits (stage weights, fold blocks, their gradients;
  held once), plus the float32 working tiles (1 forward; 2 + ``⌈p'/seg⌉ +
  seg`` checkpoint/recompute tiles backward, ``p'`` the stages left on the
  VPU) must fit the VMEM budget. A sandwich is modeled at both its widths;
* the fold — :func:`folded_stages` decides, from ``n`` alone, whether a
  butterfly's seven in-lane stages run as one 128×128 MXU block per lane
  group (:mod:`repro.kernels.butterfly`); :class:`KernelChoice` records it;
* roofline estimate — per-row FLOPs over peak FLOP/s vs per-row HBM bytes
  over HBM bandwidth; reported in :class:`KernelChoice` so benchmarks and
  the trainer can record *why* a block size was picked.

Choices are cached (``functools.lru_cache``) and overridable. The override
order matches :mod:`repro.kernels.context`: an explicit value (from an
:class:`~repro.kernels.context.ExecutionContext` or config) beats the
ambient context, which beats the env vars, which beat the model:

* ``REPRO_TUNE_BLOCK_B``   — force a batch-tile row count for butterfly and
  sandwich kernels (``ExecutionContext.block_b`` beats it).
* ``REPRO_TUNE_SEGMENT``   — force the backward checkpoint segment length
  (``ExecutionContext.segment`` beats it).
* ``REPRO_TUNE_BLOCK_Q``   — force the flash-attention q/kv block size
  (ambient ``ExecutionContext.flash_block_q`` beats it).
* ``REPRO_TUNE_VMEM_BUDGET`` — VMEM budget in bytes (default: 75% of the
  compiler's default scoped-VMEM limit, 12 of 16 MiB on v5e; ambient
  ``ExecutionContext.vmem_budget`` beats it).

Callers never pass magic numbers: an unset knob anywhere in
:mod:`repro.kernels.ops`, :mod:`repro.core.layers`, :mod:`repro.core.encdec`
or :class:`repro.configs.base.ButterflyConfig` means "ask the autotuner".
"""

from __future__ import annotations

import functools
import math
import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.butterfly import num_stages
from repro.kernels import context as exctx
from repro.launch import roofline

__all__ = [
    "FOLD_STAGES",
    "folded_stages",
    "KernelChoice",
    "tune",
    "resolve_block_b",
    "resolve_segment",
    "default_segment",
    "flash_blocks",
    "vmem_budget",
    "vmem_limit_bytes",
    "device_spec",
    "cache_entries",
    "describe",
]

# The footprint model budgets VMEM_FRACTION of the compiler's default
# scoped-VMEM limit (16 MiB on v5e): when XLA fuses a kernel call with the
# ops feeding it (inside the layer scan it does), that default is the limit
# that holds, whatever the kernel asked for. Kernels whose sublane-floor
# tile cannot fit it (the 65536-wide LM head) run unfused under the limit
# they request, VMEM_LIMIT_FRACTION of the chip's VMEM. The model only has
# to be right to within a power of two — block_b candidates are powers of
# two anyway.
VMEM_LIMIT_FRACTION = 0.75
VMEM_FRACTION = 0.75

MIN_BLOCK_B = 8
MAX_BLOCK_B = 1024

# log2 of a vreg's 128 lanes: the butterfly stages that stay inside one
# lane group, which the kernels fold into a 128x128 block per group
FOLD_STAGES = 7
_LANES = 1 << FOLD_STAGES


@dataclass(frozen=True)
class KernelChoice:
    """One autotuned kernel configuration (and why it was picked)."""

    kernel: str
    n: int                    # input width
    n_out: int                # output width (a sandwich's second butterfly)
    dtype: str
    mode: str                 # "fwd" | "bwd"
    block_b: int
    segment: int              # backward checkpoint segment (1 for fwd)
    folded: Tuple[int, ...]   # stages on the MXU, per butterfly
    vmem_bytes: int           # modeled peak VMEM footprint at this choice
    est_us_per_row: float     # roofline lower bound per activation row

    def summary(self) -> str:
        width = (f"n={self.n}" if self.kernel == "butterfly"
                 else f"n={self.n}->{self.n_out}")
        return (f"{self.kernel}/{self.mode} {width} {self.dtype}: "
                f"block_b={self.block_b} segment={self.segment} "
                f"folded={'/'.join(map(str, self.folded))} "
                f"vmem={self.vmem_bytes / 2**20:.2f}MB "
                f"roofline={self.est_us_per_row:.4f}us/row")


def device_spec() -> roofline.DeviceSpec:
    """The chip the kernels are sized for: the attached TPU's row of
    :data:`repro.launch.roofline.DEVICE_SPECS` (a TPU missing from it
    raises), or v5e — the target the CPU and interpret paths model."""
    if jax.default_backend() == "tpu":
        return roofline.device_spec(jax.devices()[0].device_kind)
    return roofline.V5E


def vmem_limit_bytes() -> int:
    """Scoped VMEM the fused kernels request from Mosaic."""
    return int(device_spec().vmem_bytes * VMEM_LIMIT_FRACTION)


def vmem_budget() -> int:
    """VMEM bytes the footprint model may spend.

    Override order: ambient :class:`~repro.kernels.context.ExecutionContext`
    (``vmem_budget`` field), then ``REPRO_TUNE_VMEM_BUDGET``, then
    ``VMEM_FRACTION`` of the chip's default scoped-VMEM limit.
    """
    ctx = exctx.current_execution()
    if ctx is not None and ctx.vmem_budget is not None:
        return int(ctx.vmem_budget)
    env = os.environ.get("REPRO_TUNE_VMEM_BUDGET", "").strip()
    if env:
        return int(env)
    return _chip_budget()


def _chip_budget() -> int:
    return int(device_spec().scoped_vmem_bytes * VMEM_FRACTION)


def folded_stages(n: int) -> int:
    """Stages of an ``n``-wide butterfly that run on the MXU: the seven
    in-lane ones (strides 1…64) where ``n ≥ 128`` and their float32
    ``(128, n)`` block, double-buffered with its gradient, fits the chip's
    VMEM budget (every width up to 4096 on v5e); none otherwise. A rule on
    the shape, not a knob: the budget overrides do not move it."""
    if n >= _LANES and 4 * _LANES * n * 4 <= _chip_budget():
        return FOLD_STAGES
    return 0


def default_segment(stages: int) -> int:
    """⌈√p⌉ — minimizes live tiles (⌈p/seg⌉ checkpoints + seg recomputed)
    of the segmented-checkpoint backward, the O(VMEM)/O(compute) knee."""
    if stages <= 1:
        return 1
    return math.isqrt(stages - 1) + 1


def _itemsize(dtype_name: str) -> int:
    return jnp.dtype(dtype_name).itemsize


def _min_block_b(dtype_name: str) -> int:
    # TPU sublane minimum per dtype: f32 (8, 128), bf16 (16, 128), int8 (32,)
    return {4: 8, 2: 16, 1: 32}.get(_itemsize(dtype_name), MIN_BLOCK_B)


def _live_tiles(stages: int, segment: int, mode: str) -> int:
    """Activation tiles of shape (block_b, n) the kernel keeps live."""
    if mode == "fwd":
        return 2                                   # x tile + out tile
    n_ckpt = -(-stages // segment)
    # checkpoints + within-segment recomputed activations + x/g/dx
    return n_ckpt + min(segment, stages) + 3


def _weight_bytes(n: int, stages: int) -> int:
    """One butterfly's stage weights as the kernels hold them: float32 rows,
    padded to whole 8-row tiles (:func:`repro.kernels.butterfly.weight_rows`)."""
    return -(-2 * stages // 8) * 8 * n * 4


def _vpu_stages(n: int) -> int:
    return num_stages(n) - folded_stages(n)


def _butterfly_bytes(n: int, block_b: int, segment: int, mode: str) -> int:
    """One butterfly's VMEM beyond the kernel's input/output tiles: its
    stage weights and fold block (with their gradients backward), each held
    once, and its float32 working tiles — 1 forward; backward the
    activation and the cotangent plus ``⌈p'/seg⌉ + seg`` checkpoint and
    recompute tiles. Traced values are one chunk wide and not counted."""
    p = num_stages(n)
    stages = _vpu_stages(n)
    blocks = _weight_bytes(n, p) + (_LANES * n * 4 if folded_stages(n) else 0)
    tile32 = block_b * n * 4
    if mode == "fwd":
        return blocks + tile32
    seg = max(1, min(segment, stages))
    return 2 * blocks + (2 + max(1, -(-stages // seg)) + seg) * tile32


def _footprint(kernel: str, n: int, n_out: int, dtype_name: str,
               block_b: int, segment: int, mode: str) -> int:
    """Modeled peak VMEM bytes for one grid step.

    Input/output tiles are double-buffered by the pipeline (``x``/``out``
    forward; ``x``/``g``/``dx`` backward); each butterfly adds
    :func:`_butterfly_bytes`; a sandwich adds its one-hot selections and
    core (and the core's gradient), held once and padded to whole (8, 128)
    float32 tiles.
    """
    item = _itemsize(dtype_name)
    tiles_in, tiles_out = (2, 2) if mode == "fwd" else (4, 2)
    total = block_b * item * (tiles_in * n + tiles_out * n_out)
    if kernel == "butterfly":
        return total + _butterfly_bytes(n, block_b, segment, mode)
    k_in, k_out = max(2, num_stages(n)), max(2, num_stages(n_out))
    core = -(-k_out // 8) * 8 * -(-k_in // _LANES) * _LANES * 4
    sel = (n * -(-k_in // _LANES) * _LANES + -(-k_out // 8) * 8 * n_out) * 4
    return (total + _butterfly_bytes(n, block_b, segment, mode)
            + _butterfly_bytes(n_out, block_b, segment, mode)
            + sel + core * (2 if mode == "bwd" else 1))


def _roofline_us_per_row(kernel: str, n: int, n_out: int, dtype_name: str,
                         mode: str) -> float:
    """max(compute, memory) roofline time per activation row, in µs."""
    item = _itemsize(dtype_name)
    widths = (n,) if kernel == "butterfly" else (n, n_out)
    # one VPU stage = 2 mul + 1 add per element; the fold block 2·128
    # per element; backward ~3x (recompute + cotangent + weight gradients)
    flops = sum(3.0 * w * _vpu_stages(w)
                + (2.0 * _LANES * w if folded_stages(w) else 0.0)
                for w in widths)
    flops *= 3.0 if mode == "bwd" else 1.0
    hbm = (n + n_out) * item * (1.5 if mode == "bwd" else 1.0)
    spec = device_spec()
    return max(flops / spec.peak_flops, hbm / spec.hbm_bw) * 1e6


@functools.lru_cache(maxsize=None)
def _tune_cached(kernel: str, n: int, n_out: int, dtype_name: str,
                 mode: str, budget: int) -> KernelChoice:
    """Pick (block_b, segment) for one (kernel, widths, dtype, direction)
    cell.

    Largest power-of-two ``block_b`` whose modeled footprint fits the VMEM
    budget, floored at the dtype's sublane minimum; ``segment`` scans the
    neighborhood of ⌈√p'⌉ (``p'``: the deeper butterfly's VPU stages) for
    the smallest live-tile count (ties go to the larger segment: fewer
    checkpoint writes). ``budget`` is part of the cache key so a changed
    ``REPRO_TUNE_VMEM_BUDGET`` is never served a stale choice.
    """
    if kernel not in ("butterfly", "sandwich"):
        raise ValueError(f"unknown tunable kernel {kernel!r}")
    if mode not in ("fwd", "bwd"):
        raise ValueError(f"unknown mode {mode!r}")
    widths = (n,) if kernel == "butterfly" else (n, n_out)
    stages = max(max(_vpu_stages(w) for w in widths), 1)

    seg0 = default_segment(stages)
    if mode == "bwd":
        cands = sorted({max(1, seg0 - 1), seg0, min(stages, seg0 + 1)})
        segment = min(cands,
                      key=lambda s: (_live_tiles(stages, s, mode), -s))
    else:
        segment = 1

    floor = _min_block_b(dtype_name)
    b = MAX_BLOCK_B
    while b >= floor:
        if _footprint(kernel, n, n_out, dtype_name, b, segment,
                      mode) <= budget:
            break
        b //= 2
    block_b = max(b, floor)

    return KernelChoice(
        kernel=kernel, n=n, n_out=n_out, dtype=dtype_name, mode=mode,
        block_b=block_b, segment=segment,
        folded=tuple(folded_stages(w) for w in widths),
        vmem_bytes=_footprint(kernel, n, n_out, dtype_name, block_b,
                              segment, mode),
        est_us_per_row=_roofline_us_per_row(kernel, n, n_out, dtype_name,
                                            mode))


def resolve_block_b(kernel: str, n: int, dtype, mode: str,
                    override: Optional[int] = None,
                    n_out: Optional[int] = None) -> int:
    """Concrete batch-tile rows: explicit override > env > autotuner."""
    if override is not None:
        return int(override)
    env = os.environ.get("REPRO_TUNE_BLOCK_B", "").strip()
    if env:
        return int(env)
    return tune(kernel, n, jnp.dtype(dtype).name, mode, n_out).block_b


def resolve_segment(stages: int, override: Optional[int] = None,
                    kernel: str = "butterfly", n: Optional[int] = None,
                    dtype=jnp.float32, n_out: Optional[int] = None) -> int:
    """Concrete checkpoint segment length for ``stages`` VPU stages,
    clamped to [1, stages]."""
    if override is not None:
        seg = int(override)
    elif os.environ.get("REPRO_TUNE_SEGMENT", "").strip():
        seg = int(os.environ["REPRO_TUNE_SEGMENT"])
    elif n is not None:
        seg = tune(kernel, n, jnp.dtype(dtype).name, "bwd", n_out).segment
    else:
        seg = default_segment(stages)
    return max(1, min(seg, max(stages, 1)))


def flash_blocks(seq_len: int, head_dim: int, dtype_name: str,
                 mode: str = "fwd") -> Tuple[int, int]:
    """(block_q, block_kv) for the flash kernels at one (S, D, dtype).

    The kernels keep the full K/V (and in backward dO/lse/delta) rows of one
    (batch·head) resident; block_q only controls the per-step tile, so pick
    the largest power of two dividing S whose q-side tiles fit what is left
    of the budget after the sequence-length-resident buffers. Overrides —
    the ambient ``ExecutionContext.flash_block_q``, then the env var — are
    read here, outside the cache, so they always win.
    """
    ctx = exctx.current_execution()
    if ctx is not None and ctx.flash_block_q is not None:
        bq = int(ctx.flash_block_q)
        return bq, bq
    env = os.environ.get("REPRO_TUNE_BLOCK_Q", "").strip()
    if env:
        bq = int(env)
        return bq, bq
    return _flash_blocks_cached(seq_len, head_dim, dtype_name, mode,
                                vmem_budget())


@functools.lru_cache(maxsize=None)
def _flash_blocks_cached(seq_len: int, head_dim: int, dtype_name: str,
                         mode: str, budget: int) -> Tuple[int, int]:
    item = _itemsize(dtype_name)
    resident = 2 * seq_len * head_dim * item        # K + V
    if mode == "bwd":
        resident += seq_len * head_dim * item       # dO sweep
        resident += 2 * seq_len * 4                 # lse + delta (f32)
    left = max(budget - resident, 0)
    for bq in (512, 256, 128, 64, 32, 16, 8):
        if seq_len % bq:
            continue
        # q tile + o/dq tile + f32 score/prob tiles against block_kv = bq
        tiles = 2 * bq * head_dim * item + 2 * bq * head_dim * 4
        tiles += 2 * bq * bq * 4
        if tiles <= left or bq == 8:
            return bq, bq
    bq = math.gcd(seq_len, 8)
    return bq, bq


# lru_cache offers no introspection of stored values, so tune() keeps its
# own registry of every decision for logging (TrainResult, benchmarks).
_CHOICES: Dict[str, str] = {}


def tune(kernel: str, n: int, dtype_name: str, mode: str = "fwd",
         n_out: Optional[int] = None) -> KernelChoice:
    """The choice for one kernel call: ``n`` is the input width, ``n_out``
    a sandwich's output width (default ``n``)."""
    n_out = n if n_out is None or kernel == "butterfly" else n_out
    # env (budget) is read here, outside the cache, so overrides set after
    # the first query still take effect
    choice = _tune_cached(kernel, n, n_out, dtype_name, mode, vmem_budget())
    width = f"n{n}" if n_out == n else f"n{n}-{n_out}"
    _CHOICES[f"{kernel}/{mode}/{width}/{dtype_name}"] = choice.summary()
    return choice


def cache_entries() -> Dict[str, str]:
    """Every choice made so far (key -> one-line summary)."""
    return dict(_CHOICES)


def describe() -> str:
    """One-line-per-choice summary of every tuning decision this process."""
    return "; ".join(sorted(_CHOICES.values())) or "no kernel tuning queried"
