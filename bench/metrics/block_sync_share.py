"""block_sync_share (%): share of the stream engine's per-block host time
spent in the emit callback (span `block.emit`, where the block's labels are
fetched to the host and the device is waited on) over `block.consume`."""


def read(ctx):
    if ctx.kind != "fit":
        return None
    consume = sum(s.dur for s in ctx.spans if s.name == "block.consume")
    if consume <= 0:
        return None
    emit = sum(s.dur for s in ctx.spans if s.name == "block.emit")
    return 100.0 * emit / consume
