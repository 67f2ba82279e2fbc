"""block_host_ms (ms): mean host time of one block in the stream engine's
pipelined loop (span `block.consume`: the map dispatch, the emit callback
with its label fetch, and the combine; the wait on the prefetch queue is
outside it), over every consumer of the window."""


def read(ctx):
    if ctx.kind != "fit":
        return None
    durs = [s.dur for s in ctx.spans if s.name == "block.consume"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
