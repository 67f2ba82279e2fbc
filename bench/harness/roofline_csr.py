"""Operations and least bytes of the sparse APNC embed of one CSR block, kept
with the benchmark beside roofline.py (whose peaks it shares).

One block of b rows holding nnz nonzeros, embedded against l landmarks into
m columns:

  operations   2 nnz l   the gram's cross term X L^T from the nonzeros
               2 b l m   the contraction K R^T
  least bytes  the CSR block 4 (2 nnz + b + 1)  (values, column ids, row
               pointers), the landmarks as the CSR rows they are,
               4 (2 l r + l + 1) with r = nnz / b nonzeros a row, R 4 m l,
               and Y out 4 b m.

Elementwise work (norms, exp) is not counted. A dense table of L^T is the
implementation's choice, not part of the least.
"""
from __future__ import annotations

from .roofline import F32


def sparse_embed(nnz: float, b: float, l: int, m: int) -> tuple[float, float]:
    """(operations, least bytes) of one sparse embed of b rows, nnz nonzeros."""
    ops = 2.0 * nnz * l + 2.0 * b * l * m
    per_row = nnz / b if b else 0.0
    nbytes = F32 * ((2 * nnz + b + 1)            # the CSR block
                    + (2 * l * per_row + l + 1)  # the landmarks as CSR rows
                    + m * l                      # R
                    + b * m)                     # Y out
    return ops, nbytes
