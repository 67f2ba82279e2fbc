"""csr_block_fill (%): share of the nonzero slots the device computes over
that hold real nonzeros and not padding: counter `sparse.nnz` over counter
`sparse.slots`, summed over every CSR block the Lloyd step plan stepped or
assigned in the window (each block is padded to its store's one capacity,
the largest block rounded up to a lane multiple)."""


def read(ctx):
    if ctx.kind != "fit":
        return None
    slots = ctx.counters.get("sparse.slots")
    if not slots:
        return None
    return 100.0 * ctx.counters.get("sparse.nnz", 0.0) / slots
