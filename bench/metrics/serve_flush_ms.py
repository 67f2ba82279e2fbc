"""serve_flush_ms (ms): mean host time of one micro-batch flush (span
`serve.flush`: stack and cast, the fused embed+assign dispatch with its
label fetch, and the per-request deliveries)."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    durs = [s.dur for s in ctx.spans if s.name == "serve.flush"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
