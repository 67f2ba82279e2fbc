"""Operations and least bytes of the fused Lloyd steps, and the chip's peaks.

The counts are the algorithm's, from the shapes alone, so a roofline share
reads the same work whatever precision or kernel carries it out:

  APNC step, one block of b rows (d inputs, l landmarks, m embedding
  columns, k centroids):
      2bdl  the gram's cross term X L^T
      2blm  the contraction K R^T
      2bmk  the distances' cross term Y C^T
      bm    the per-cluster sums Z (one add per embedded value)
  RFF step (h = half the embedding width, m = 2h):
      2bdh  the projection X W
      2bmk + bm as above.
  Y-mode assign step, one pass over b already-embedded rows (the local
  driver's Lloyd iteration):
      2bmk + bm as above.

Elementwise work (norms, exp, cos, sin, argmin) is not counted. The least
bytes are what one call must read and write in HBM at float32: the block
(X, or Y for the assign step), the operands (landmarks and R, or W; the
centroids), and Z, g, the labels and the cost. A share is the least time,
max(operations / peak FLOP/s, bytes / peak bytes/s), over the measured
kernel time; the FLOP/s peak is the chip's bf16 peak.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def apnc_step(b: float, d: int, l: int, m: int, k: int) -> tuple[float, float]:
    """(operations, least bytes) of one fused APNC step over b rows."""
    ops = 2.0 * b * d * l + 2.0 * b * l * m + 2.0 * b * m * k + b * m
    nbytes = F32 * (b * d + l * d + m * l + k * m  # block, L, R, C in
                    + k * m + k + b + 1)            # Z, g, labels, cost out
    return ops, nbytes


def rff_step(b: float, d: int, h: int, k: int) -> tuple[float, float]:
    """(operations, least bytes) of one fused RFF step over b rows."""
    m = 2 * h
    ops = 2.0 * b * d * h + 2.0 * b * m * k + b * m
    nbytes = F32 * (b * d + d * h + k * m + k * m + k + b + 1)
    return ops, nbytes


def assign_step(b: float, m: int, k: int) -> tuple[float, float]:
    """(operations, least bytes) of one Y-mode assign step over b embedded
    rows of width m."""
    ops = 2.0 * b * m * k + b * m
    nbytes = F32 * (b * m + k * m + k * m + k + b + 1)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
