"""One run of one cell: set-up, window, reference check, result line."""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time
import types

from . import spec
from .spec import ROOT

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one (JAX then reads it itself)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Executables built (compiled, or loaded from the persistent cache)
    while `active`, by function name."""

    def __init__(self):
        import jax

        self.active = False
        self.names: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, fun_name="?", **_):
        if self.active and event == COMPILE_EVENT:
            self.names[fun_name] = self.names.get(fun_name, 0) + 1


_COUNTER: list = []


def _compile_counter() -> CompileCounter:
    if not _COUNTER:  # one listener per process, however many runs it makes
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def _limits(cell_name: str) -> dict:
    return json.loads((spec.BENCH_DIR / "limits" / f"{cell_name}.json").read_text())


def judge(numbers: list, limits: dict) -> tuple[bool, dict]:
    """Worst reading of each number over the compared answers, against its
    limit. A number with no limit is an error; a limit with no reading, or
    no answer compared at all, fails the run."""
    checks = {}
    for name, lim in limits.items():
        vals = [n[name] for n in numbers if name in n]
        if vals:
            v = max(vals)
            checks[name] = {"value": v if math.isfinite(v) else 1e300,
                            "limit": lim["limit"]}
    unknown = {k for n in numbers for k in n} - set(limits)
    if unknown:
        raise KeyError(f"compared numbers without a limit: {sorted(unknown)}")
    ok = bool(numbers) and all(c["value"] <= c["limit"] for c in checks.values())
    return ok and set(limits) <= set(checks), checks


def run_cell(cell, seed: int, seconds: float, traced: bool, *, t_process: float,
             require_tpu: bool = True, policy=None, max_fits: int | None = None,
             compile_cache: bool = True, window_fits: int | None = None,
             reference_control: bool = False) -> dict:
    """Everything but printing. `policy`, `require_tpu`, `compile_cache`,
    `window_fits` (answers after which the window closes early) and
    `max_fits` (answers compared) exist for the control runs and the CPU
    tests; a benchmark run leaves them alone."""
    import jax

    device = device_info(cell.chips, require_tpu)
    if compile_cache:
        enable_compile_cache()
    from repro import obs

    compiles = _compile_counter()
    compiles.names = {}
    kind = cell.traffic["kind"]
    traffic = spec.traffic_module(kind)
    state = traffic.setup(cell, seed, policy=policy)
    # Set-up's objects (rows, compiled programs, the fitted model) live for
    # the whole run: keep them out of the collector's full passes, whose
    # pauses would otherwise grow with everything set-up made.
    gc.collect()
    gc.freeze()

    if traced:
        from . import trace

        obs.enable_tracing()
        obs.clear_trace()
        tdir = TRACE_DIR / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
        trace.start(str(tdir))
    before = obs.snapshot()
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    compiles.active = True
    with jax.profiler.TraceAnnotation("bench.window"):
        window = traffic.run_window(state, seconds, window_fits)
    compiles.active = False
    gc.unfreeze()
    counters = obs.delta(before, obs.snapshot())
    tr = None
    if traced:
        spans = [s for s in obs.TRACER.spans() if s.t0 >= t_window]
        obs.disable_tracing()
        tr = trace.read(trace.stop(str(tdir)))
    device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)

    out = traffic.finish(cell, state, window, max_fits, reference_control)
    e2e = dict(out.e2e, setup_s=setup_s)
    ok, checks = judge(out.numbers, _limits(cell.name))

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if traced:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        ctx = types.SimpleNamespace(
            cell=cell, kind=kind, trace=tr, device=device, counters=counters,
            spans=spans, window=window, window_s=window.t_close - t_window)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": _named_gaps(tr, spans, t_window)}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}
        breakdown = None
    result = {"correct": bool(ok), "attempted": out.attempted,
              "failed": int(out.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # Executables built (compiled, or loaded from the persistent cache)
    # while the window ran, and by which function.
    result["compiles_in_window"] = sum(compiles.names.values())
    result["run"] = dict(out.extra, built_in_window=compiles.names)
    result["checks"] = checks
    return result


def _named_gaps(tr, spans, t_window: float, limit: int = 10) -> list:
    """The longest device idle gaps, each named by the innermost host span
    of the program that covers its middle ("no span" when none does)."""
    offset_ns = tr.window[0] - t_window * 1e9  # perf_counter -> trace clock
    timed = [(s.t0 * 1e9 + offset_ns, (s.t0 + s.dur) * 1e9 + offset_ns, s.name)
             for s in spans if getattr(s, "dur", None) is not None]
    out = []
    for start, dur in sorted(tr.gaps(), key=lambda g: -g[1])[:limit]:
        mid = start + dur / 2
        covering = [(b - a, name) for a, b, name in timed if a <= mid <= b]
        name = min(covering)[1] if covering else "no span"
        out.append([name, dur * 1e-9])
    return out


def main(workload: str, seed: int, seconds: float, traced: bool, *,
         t_process: float) -> int:
    cell = spec.load_cell(workload)
    try:
        result = run_cell(cell, seed, seconds, traced, t_process=t_process)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
