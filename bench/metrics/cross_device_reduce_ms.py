"""cross_device_reduce_ms (ms): host time of the sharded stream's one
cross-device (Z, g) reduction per Lloyd iteration (span
`reduce.cross_device`), summed over the window and divided by its Lloyd
iterations (spans `lloyd.iter`). Only a fit sharded over devices has it."""


def read(ctx):
    if ctx.kind != "fit" or ctx.cell.traffic.get("backend") != "stream_shard":
        return None
    reduce_s = [s.dur for s in ctx.spans
                if s.name == "reduce.cross_device" and s.dur is not None]
    iters = sum(1 for s in ctx.spans if s.name == "lloyd.iter")
    if not reduce_s or iters == 0:
        return None
    return 1e3 * sum(reduce_s) / iters
