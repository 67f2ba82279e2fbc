"""Find a cell's pieces by name: BENCHMARK.json, then one file each.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name. A configuration `c` is `configs/c.json` (its sizes) with its plain
reference `configs/c.py` beside it; a traffic mix `t` is `traffic/t.json`;
a per-layer metric `p` is read by `metrics/p.py`. A traffic file's `kind`
names the generator that reads it, `harness/<kind>_traffic.py`; a
configuration's `rows` (default "mixture") names the row format that makes
and holds its rows, `harness/rows_<rows>.py`. Adding a cell means adding
those files and the entries in BENCHMARK.json.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import NamedTuple

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    reference: ModuleType  # configs/<config>.py
    end_to_end: list  # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_module(path: Path) -> ModuleType:
    """Import a file under bench/ by path (its name may hold '.' or '-')."""
    mod_name = "bench_file_" + "".join(
        ch if ch.isalnum() else "_" for ch in str(path.relative_to(BENCH_DIR))
    )
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Outcome(NamedTuple):
    """What a traffic generator hands back once its window has closed."""

    e2e: dict  # end-to-end metric name -> value (None: nothing to report)
    attempted: int
    failed: int
    numbers: list  # one dict of compared numbers per compared answer
    extra: dict  # shown under the result line's "run"


def traffic_module(kind: str) -> ModuleType:
    """The generator of a traffic kind: `harness/<kind>_traffic.py`.

    Each has setup(cell, seed, policy) -> state, run_window(state, seconds,
    max_answers) -> window (with `t_close`), and finish(cell, state, window,
    max_compared, reference_control) -> Outcome."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", kind):
        raise ValueError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"{__package__}.{kind}_traffic")


def rows_module(fmt: str) -> ModuleType:
    """The row format `fmt`: `harness/rows_<fmt>.py` (see rows_mixture.py
    for what one provides)."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", fmt):
        raise ValueError(f"bad row format {fmt!r}")
    return importlib.import_module(f"{__package__}.rows_{fmt}")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Path | None = None) -> Cell:
    spec = json.loads((benchmark or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = ROOT / configs[w["config"]]["file"]
    config = json.loads(cfg_file.read_text())
    traffic = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        reference=load_module(cfg_file.with_suffix(".py")),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)],
    )


def metric_reader(metric_name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{metric_name}.py")
