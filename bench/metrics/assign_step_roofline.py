"""assign_step_roofline (%): the Y-mode assign kernel's share of its roofline
over the fit window, in a cell whose fits run the resident `local` driver.

That driver embeds X once a fit and then runs `core.lloyd.lloyd`, whose every
iteration, and whose final assignment, is one call of the assign kernel over
all n embedded rows: the `tpu_custom_call` that `ops._assign_padded` wraps,
as a v5e trace names it. Each event is counted as one pass over n rows of the
embedding's width (q·m, or 2m for rff) against k centroids
(bench/harness/roofline.py `assign_step`). The share is the sum of the least
times over the kernel time. The pad of Y to the kernel's lanes and the cost,
which the step reduces in separate XLA ops, are not in the kernel time. No
event, or a cell that streams blocks, no reading."""
from bench.harness import roofline

KERNEL = ("_assign_padded", "tpu_custom_call")


def read(ctx):
    if ctx.kind != "fit" or ctx.trace is None \
            or ctx.cell.traffic.get("backend") != "local":
        return None
    events, secs = ctx.trace.kernel(KERNEL)
    if events == 0 or secs <= 0:
        return None
    cfg = ctx.cell.config
    width = 2 * cfg["m"] if cfg["method"] == "rff" else cfg["m"] * cfg["q"]
    ops, nbytes = roofline.assign_step(cfg["n"], width, cfg["k"])
    least, _ = roofline.least_seconds(ops, nbytes, roofline.peaks(ctx.device["kind"]))
    return 100.0 * events * least / secs
