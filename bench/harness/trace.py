"""Profiler trace -> device busy time, idle gaps and kernel time.

A traced run records the window with the JAX profiler (the Python tracer
off) inside a `TraceAnnotation` named `WINDOW`, which puts the window's
bounds on the trace's own clock. The reduction reads the `.xplane.pb` with
`jax.profiler.ProfileData` and nothing of the program:

  * device planes are those named `/device:TPU:<n>` (no other platform is
    measured); their op line is `XLA Ops`;
  * busy time of a device is the union of its op intervals inside the
    window; the idle share is 1 - busy / window;
  * an idle gap is an interval of the window in which no op ran on a device;
  * kernel time is the summed duration of the op events whose text (name and
    string stats) holds every given fragment. On a v5e an op event's name is
    its HLO instruction; a Pallas kernel is a `tpu_custom_call` named after
    the jitted function that wraps it (`%_fused_rff_step_padded.1 = ...`).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


HOST_TRACER_LEVEL = 1  # the window annotation and the runtime's main events


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = HOST_TRACER_LEVEL
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop the trace; returns the path of the newest `.xplane.pb` written."""
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    text: str  # name and string stats, for kernel matching


@dataclasses.dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of the WINDOW annotation
    devices: dict  # plane name -> [Op] inside the window, by start

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran any op."""
        used = [ops for ops in self.devices.values() if ops]
        if not used:
            return 0.0
        return sum(_union_ns(ops, self.window) for ops in used) * 1e-9 / len(used)

    def kernel(self, fragments) -> tuple[int, float]:
        """(events, device seconds) of ops whose text holds all `fragments`."""
        count, ns = 0, 0.0
        for ops in self.devices.values():
            for op in ops:
                if all(f in op.text for f in fragments):
                    count += 1
                    ns += op.dur_ns
        return count, ns * 1e-9

    def top_ops(self, limit: int = 10) -> list:
        """[[op, seconds]] of the ops that took most device time, an op
        named by its HLO instruction without the numeric suffix."""
        tot: dict = {}
        for ops in self.devices.values():
            for op in ops:
                name = short_name(op.name)
                tot[name] = tot.get(name, 0.0) + op.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:limit]]

    def gaps(self) -> list:
        """[(start_ns, dur_ns)] of idle intervals on the busiest device."""
        used = [ops for ops in self.devices.values() if ops]
        if not used:
            return [(self.window[0], self.window[1] - self.window[0])]
        ops = max(used, key=lambda o: _union_ns(o, self.window))
        out, cursor = [], self.window[0]
        for a, b in _merged(ops, self.window):
            if a > cursor:
                out.append((cursor, a - cursor))
            cursor = max(cursor, b)
        if self.window[1] > cursor:
            out.append((cursor, self.window[1] - cursor))
        return out


def short_name(name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _merged(ops, window):
    lo, hi = window
    spans = sorted((max(op.start_ns, lo), min(op.start_ns + op.dur_ns, hi))
                   for op in ops)
    out: list = []
    for a, b in spans:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _union_ns(ops, window) -> float:
    return sum(b - a for a, b in _merged(ops, window))


def _text(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def read(path: str, window_name: str = WINDOW) -> Trace:
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    marks = (ev for plane in prof.planes if not plane.name.startswith(DEVICE_PREFIX)
             for line in plane.lines for ev in line.events if ev.name == window_name)
    ev = next(marks, None)
    if ev is None:
        raise ValueError(f"no {window_name!r} annotation in {path}")
    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    devices = {}
    for plane in prof.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if end > window[0] and ev.start_ns < window[1]:
                    ops.append(Op(ev.name, ev.start_ns, ev.duration_ns, _text(ev)))
        devices[plane.name] = sorted(ops, key=lambda o: o.start_ns)
    return Trace(window, devices)


def describe(path: str, per_line: int = 5) -> list[str]:
    """Plain-text outline of a trace: planes, lines, sample events and their
    stats. For looking at a trace by hand before reading it in code."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    out = []
    for plane in prof.planes:
        lines = list(plane.lines)
        out.append(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                stats = [(k, v) for k, v in ev.stats][:8]
                out.append(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} {stats}")
    return out
