"""host_stall_share.fit (%): share of the fit window in which a ready
Python thread waited more than 20 ms to run: the summed `process.stall`
spans of the tracer's watchdog (each from when it was due to wake to when it
woke, counted when more than 20 ms late) over the window. The wait is for
the interpreter lock held by another thread, for the collector, or for the
OS; the metric does not tell them apart, and it grows with the number of
busy Python threads. Read only where the watchdog ran (counter
`process.watchdog_wakes`), so a window with no stall reads 0."""


def read(ctx):
    if ctx.kind != "fit" or not ctx.counters.get("process.watchdog_wakes") \
            or ctx.window_s <= 0:
        return None
    stalled = sum(s.dur for s in ctx.spans if s.name == "process.stall")
    return 100.0 * stalled / ctx.window_s
