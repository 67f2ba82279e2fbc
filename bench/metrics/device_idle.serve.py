"""device_idle.serve (%): share of the serving window in which no op ran on
the device, from the profiler trace (1 - union of op intervals / window)."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or ctx.trace.window_s <= 0 \
            or not any(ctx.trace.devices.values()):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
