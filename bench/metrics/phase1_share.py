"""phase1_share (%): the facade's phase 1 (reservoir pass, embedding fit,
k-means++ seeding; `FitReport.phases` reservoir + embed_fit + seed) over the
wall time of the completed fits."""

PHASE1 = ("reservoir", "embed_fit", "seed")


def read(ctx):
    if ctx.kind != "fit" or not ctx.window.fit_s:
        return None
    p1 = sum(p.get(name, 0.0) for p in ctx.window.phases for name in PHASE1)
    return 100.0 * p1 / sum(ctx.window.fit_s)
