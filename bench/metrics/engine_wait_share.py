"""engine_wait_share (%): time the stream engine's consumers spent blocked
on an empty prefetch queue (counter `engine.prefetch_stall_s`, summed over
the devices' consumers) over the window times the number of consumers."""


def read(ctx):
    if ctx.kind != "fit":
        return None
    stall = ctx.counters.get("engine.prefetch_stall_s")
    if stall is None:
        return None
    consumers = ctx.cell.chips if ctx.cell.traffic["backend"] == "stream_shard" else 1
    return 100.0 * stall / (ctx.window_s * consumers)
