"""serve_device_share (%): share of the flushes' host time spent in the
model's process call (span `serve.device`: pad, host-to-device copy,
dispatch and the label fetch) over `serve.flush`."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    flush = sum(s.dur for s in ctx.spans if s.name == "serve.flush")
    if flush <= 0:
        return None
    device = sum(s.dur for s in ctx.spans if s.name == "serve.device")
    return 100.0 * device / flush
