"""Cells of BENCHMARK.json cut to sizes a CPU test run can hold.

Only the row count shrinks; every width is the configuration's own. Everything
else (traffic, reference, limits) is the cell's own.
"""
from __future__ import annotations

import dataclasses

from bench.harness import spec

SMALL = {
    "covtype-rff": {"n": 3 * 4096 + 1000},
    "imagenet-nystrom": {"n": 3 * 4096 + 1000},
}


def small_cell(name: str, **traffic):
    cell = spec.load_cell(name)
    cfg = dict(cell.config)
    cfg.update(SMALL[cfg["name"]])
    tr = dict(cell.traffic)
    if tr["kind"] == "serve":
        tr.update(fit_rows=8192, held_out_rows=4096, rate_per_s=1000.0)
    tr.update(traffic)
    return dataclasses.replace(cell, config=cfg, traffic=tr)
