"""fused_step_roofline (%): the fused Lloyd step kernel's share of its
roofline over the fit window.

Kernel time is the summed device time of the trace's events of the fused
step: the `tpu_custom_call` inside `ops._fused_apnc_step_padded` or
`ops._fused_rff_step_padded`, as a v5e trace names it.
Each event is one block; its operations and least bytes are counted for the
mean block of the store (n / blocks rows), by bench/harness/roofline.py. The
share is the sum of the least times over the kernel time. No event, no
reading."""
from bench.harness import roofline

KERNELS = {"nystrom": ("_fused_apnc_step_padded", "tpu_custom_call"),
           "rff": ("_fused_rff_step_padded", "tpu_custom_call")}


def read(ctx):
    if ctx.kind != "fit" or ctx.trace is None:
        return None
    cfg = ctx.cell.config
    frags = KERNELS.get(cfg["method"])
    if frags is None:
        return None
    events, secs = ctx.trace.kernel(frags)
    if events == 0 or secs <= 0:
        return None
    blocks = -(-cfg["n"] // cfg["block_rows"])
    b = cfg["n"] / blocks
    if cfg["method"] == "rff":
        ops, nbytes = roofline.rff_step(b, cfg["d"], cfg["m"], cfg["k"])
    else:
        ops, nbytes = roofline.apnc_step(b, cfg["d"], cfg["l"], cfg["m"], cfg["k"])
    least, _ = roofline.least_seconds(ops, nbytes, roofline.peaks(ctx.device["kind"]))
    return 100.0 * events * least / secs
