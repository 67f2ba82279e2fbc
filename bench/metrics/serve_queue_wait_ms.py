"""serve_queue_wait_ms (ms): mean time a request waited before its batch's
flush began: in the tier's intake until the dispatcher drained it (counter
`serve.intake_wait_s`), then in its model's batcher (counter
`serve.batch_wait_s`), summed over the window's requests and divided by the
rows flushed (histogram `serve.batch_size` sum)."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    intake = ctx.counters.get("serve.intake_wait_s")
    batch = ctx.counters.get("serve.batch_wait_s")
    hist = ctx.counters.get("serve.batch_size")
    if intake is None or batch is None or not hist or hist.get("sum", 0) <= 0:
        return None
    return 1e3 * (intake + batch) / hist["sum"]
