"""sparse_embed_roofline (%): the sparse APNC embed's share of its roofline
over the fit window.

Kernel time is the device time of the program `ops._sparse_embed_block`:
XLA ops (a gather and sorted segment sums, no Pallas kernel), dispatched as
a jitted function of their own, so the trace's `XLA Modules` line names each
run of it (`jit__sparse_embed_block(...)`) where its op events carry no such
name. The Y-mode assign that follows (`_assign_padded`) is another program
and is not counted; the seeding pool's one embed a fit is (1,024 rows, about
one block in 50 of a fit's).

The least time is that of every CSR block the plan stepped or assigned
(counter `sparse.blocks`) with the window's nonzeros (counter `sparse.nnz`)
and the mean block's rows (n over the store's blocks, exact over whole
passes), by bench/harness/roofline_csr.py, at the chip's peaks. No module
run or no CSR block, no reading."""
import glob
import os

from bench.harness import roofline, roofline_csr

FRAGMENT = "_sparse_embed_block"
MODULES_LINE = "XLA Modules"


def trace_file(ctx):
    """The run's `.xplane.pb` (the runner traces a cell into its own dir)."""
    from bench.harness import runner

    found = glob.glob(os.path.join(str(runner.TRACE_DIR / ctx.cell.name), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def module_events(path):
    """[(name, start_ns, dur_ns)] of the device planes' `XLA Modules` line."""
    from jax.profiler import ProfileData

    from bench.harness.trace import DEVICE_PREFIX

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    out.extend((ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events)
    return out


def read(ctx):
    if ctx.kind != "fit" or ctx.trace is None:
        return None
    blocks = ctx.counters.get("sparse.blocks")
    if not blocks:
        return None
    path = trace_file(ctx)
    if path is None:
        return None
    lo, hi = ctx.trace.window
    secs = sum(dur for name, start, dur in module_events(path)
               if FRAGMENT in name and start + dur > lo and start < hi) * 1e-9
    if secs <= 0:
        return None
    cfg = ctx.cell.config
    b = cfg["n"] / -(-cfg["n"] // cfg["block_rows"])
    nnz = ctx.counters["sparse.nnz"] / blocks
    ops, nbytes = roofline_csr.sparse_embed(nnz, b, cfg["l"], cfg["m"])
    least, _ = roofline.least_seconds(ops, nbytes, roofline.peaks(ctx.device["kind"]))
    return 100.0 * blocks * least / secs
