"""Faults planted in the program from outside, the way a wrong change to it
would break it. The comparison that decides `correct` has to catch each one
a cell can have; the tests in bench/tests plant them at a small size on the
CPU and `bench/calibrate.py --fault <name>` at the cell's own size on the
chip.

Each fault takes a patcher with `setattr(obj, name, value)` (pytest's
`monkeypatch`, or `Patcher` below).
"""
from __future__ import annotations


class Patcher:
    """setattr with an undo, for use outside pytest."""

    def __init__(self):
        self._undo = []

    def setattr(self, obj, name, value):
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self._undo:
            obj, name, value = self._undo.pop()
            setattr(obj, name, value)


def state_unchanged(mp):
    """Every Lloyd update returns the centroids it was given: the stream
    drivers' and the resident driver's (`core.lloyd.lloyd`)."""
    import importlib

    import repro.stream.lloyd as lloyd
    import repro.stream.sharded as sharded

    # the module, not the `lloyd` function that repro.core exports by its name
    core_lloyd = importlib.import_module("repro.core.lloyd")

    def keep(Z, g, prev):
        return prev

    mp.setattr(lloyd, "centroid_update", keep)
    mp.setattr(sharded, "centroid_update", keep)
    mp.setattr(core_lloyd, "centroid_update", keep)


def half_batch(mp):
    """Each block's (Z, g) sums only its first half of rows: the mean taken
    over half of the batch."""
    from repro.kernels.ops import LloydStepPlan

    step = LloydStepPlan.step

    def half(self, block, centroids):
        Z, g, labels, cost = step(self, block, centroids)
        Zh, gh, _, _ = step(self, block[: block.shape[0] // 2], centroids)
        return Zh, gh, labels, cost

    mp.setattr(LloydStepPlan, "step", half)


def altered_labels(mp):
    """A fit's final pass returns one wrong label a block."""
    from repro.kernels.ops import LloydStepPlan

    assign = LloydStepPlan.assign

    def altered(self, block, centroids):
        labels, cost = assign(self, block, centroids)
        k = centroids.shape[0]
        return labels.at[0].set((labels[0] + 1) % k), cost

    mp.setattr(LloydStepPlan, "assign", altered)


def altered_served(mp):
    """The serving path returns one wrong label a flush."""
    from repro.kernels import ops

    predict = ops.predict_block

    def altered(X, params, centroids, policy=None):
        labels = predict(X, params, centroids, policy=policy)
        return labels.at[0].set((labels[0] + 1) % centroids.shape[0])

    mp.setattr(ops, "predict_block", altered)


def no_exchange(mp):
    """The cross-device reduction left out: every device's statistics are
    replaced by device 0's, so each update sees one device's rows."""
    import jax

    import repro.stream.sharded as sharded

    real = sharded.cross_device_sum

    def local_only(accs, devices):
        host = jax.device_get(accs[0])
        return real([jax.device_put(host, d) for d in devices], devices)

    mp.setattr(sharded, "cross_device_sum", local_only)


FIT = {"state_unchanged": state_unchanged, "half_batch": half_batch,
       "altered_labels": altered_labels}
SERVE = {"altered_served": altered_served}
SHARD = {"no_exchange": no_exchange}
ALL = {**FIT, **SERVE, **SHARD}
