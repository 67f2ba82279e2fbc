"""serve_batch_fill (%): mean micro-batch size of the window's flushes
(histogram `serve.batch_size`) over the registry's `max_batch`."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    hist = ctx.counters.get("serve.batch_size")
    if not hist or hist.get("count", 0) <= 0:
        return None
    return 100.0 * hist["sum"] / hist["count"] / ctx.cell.traffic["max_batch"]
