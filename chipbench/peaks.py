"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline or MFU against a guessed peak is no number."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops: float          # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float         # HBM bandwidth, bytes/s
    hbm_bytes: int             # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite", bf16_flops=197e12, hbm_bytes_s=819e9,
        hbm_bytes=16 * 10 ** 9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {kind!r} "
                         f"(known: {sorted(PEAKS)}); add a row with its "
                         f"source to chipbench/peaks.py") from None
