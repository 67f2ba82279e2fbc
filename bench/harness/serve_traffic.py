"""Traffic kind "serve": one row per request, Poisson arrivals at a rate
fixed in the traffic file, into `ServingTier.submit`.

Set-up fits the served model through `KernelKMeans` on `fit_rows` rows of
the configuration's mixture, under the run's compute policy, registers it
under the traffic file's `model` name (which compiles and runs its one batch
shape), starts the tier with the traffic file's admission bound
(`max_inflight`) and offers a short burst at the cell's rate. Every seed
offers the same work: the same number of requests and the same set of gaps
between them; `--seed` draws their order and the rows they carry. Each
request is timed from when it was due, not from when the generator got to
it, so a stalled generator or server shows in the tail; how late the
generator ran is reported beside it. A shed or failed request counts as over
any limit: its latency is the whole time it was given (the window plus the
drain wait).

After the window the served model is held to the numbers of a fit cell (its
embedding, labels, inertia and centroids against the plain reference on its
fit rows, as fit_traffic does), and every answered label to the reference's
nearest centroid.

Deliveries are written into preallocated arrays indexed by request id, so
the benchmark's own bookkeeping adds no Python objects per request for the
collector to walk while the window runs.

This replaces `repro.serving.loadgen.run_open_loop` for the benchmark: that
one times from the actual submit and does not report its own lateness.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from . import data, rows_mixture
from .fit_traffic import (FIT_KEY_BASE, _record, answer, estimator, fit_key,
                          next_centroids)
from .spec import Outcome

WARM_S = 1.0
DRAIN_S = 60.0
GAPS_SEED = 0  # the one set of arrival gaps every seed shares


class Deliveries:
    """Per-request delivery records of one window. Written only by the
    tier's dispatcher thread (its `on_response` callback); read by the
    generator's thread once the answers are in."""

    def __init__(self):
        self.reset(0)

    def reset(self, n: int) -> None:
        self.t = np.full(n, np.nan)
        self.label = np.full(n, -1, np.int64)
        self.error = np.zeros(n, bool)
        self.count = np.zeros(n, np.int64)
        self.answered = 0
        self.stray = 0  # responses carrying an id this window never sent

    def on_response(self, resp) -> None:
        t = time.perf_counter()
        i = resp.request_id
        if 0 <= i < self.count.shape[0]:
            if self.count[i] == 0:
                self.t[i] = t
                self.label[i] = resp.label
                self.error[i] = resp.error is not None
            self.count[i] += 1
        else:
            self.stray += 1
        self.answered += 1


@dataclasses.dataclass
class ServeSetup:
    config: dict
    traffic: dict
    seed: int
    X_fit: np.ndarray
    X_held: np.ndarray
    fit_store: object  # BlockStore of X_fit
    fitted: object  # FitAnswer of the served model's fit
    estimator: object  # the served model's fitted KernelKMeans
    tier: object
    got: Deliveries


def arrivals(seed: int, rate: float, seconds: float, rows: int, tag: int):
    """(due offsets in seconds, row index) of every request in the window.

    round(rate * seconds) requests, whose gaps are one fixed set of
    exponential draws scaled so the last falls at `seconds`: the arrivals of
    a Poisson process given its count. The seed draws only their order, so
    every seed offers the same work. Rows follow a permutation drawn from the
    seed, so no row is served twice before every held-out row has been served
    once."""
    n = max(1, round(rate * seconds))
    gaps = np.random.default_rng([GAPS_SEED, tag]).exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng([seed, tag])
    due = np.cumsum(rng.permutation(gaps))
    return due, np.resize(rng.permutation(rows), n)


def setup(cell, seed: int, policy=None) -> ServeSetup:
    from repro.api import ModelRegistry, ServingTier
    from repro.stream.blockstore import BlockStore

    cfg, tr = cell.config, cell.traffic
    X_fit = data.make_rows(seed, cfg, tr["fit_rows"], data.STREAM_FIT)
    X_held = data.make_rows(seed, cfg, tr["held_out_rows"], data.STREAM_HELD_OUT)
    store = BlockStore.from_array(X_fit, cfg["block_rows"])
    est = estimator(cfg, tr["fit_backend"], policy).fit(
        store, key=fit_key(seed, FIT_KEY_BASE))
    registry = ModelRegistry(max_batch=tr["max_batch"], policy=policy)
    registry.register(tr["model"], est.model_)  # compiles and runs the batch shape
    got = Deliveries()
    tier = ServingTier(registry, max_inflight=tr["max_inflight"],
                       on_response=got.on_response).start()
    s = ServeSetup(cfg, tr, seed, X_fit, X_held, store,
                   answer(est, 0, FIT_KEY_BASE), est, tier, got)
    warm = offer(s, WARM_S, tag=1)  # warm the threads and queues
    wait_answers(s, 60.0, int((~warm.shed).sum()))
    return s


@dataclasses.dataclass
class ServeWindow:
    due: np.ndarray  # absolute due time of each request
    rows: np.ndarray
    submitted: np.ndarray  # absolute time the generator submitted it
    shed: np.ndarray  # bool
    t_start: float
    t_end: float  # last arrival's submit
    t_close: float = 0.0  # the last answer came in, or the drain wait ended


def offer(s: ServeSetup, seconds: float, tag: int) -> ServeWindow:
    """Submit one window's requests (ids 0..n-1) on their schedule."""
    from repro.serving.admission import Shed

    model = s.traffic["model"]
    due_off, rows = arrivals(s.seed, s.traffic["rate_per_s"], seconds,
                             s.X_held.shape[0], tag)
    n = due_off.shape[0]
    s.got.reset(n)
    submitted = np.zeros(n)
    shed = np.zeros(n, bool)
    t_start = time.perf_counter()
    due = t_start + due_off
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        submitted[i] = time.perf_counter()
        try:
            s.tier.submit(i, s.X_held[rows[i]], model)
        except Shed:
            shed[i] = True
    return ServeWindow(due, rows, submitted, shed, t_start, time.perf_counter())


def wait_answers(s: ServeSetup, limit_s: float, expected: int) -> None:
    """Wait until `expected` responses came in (or `limit_s` passes)."""
    end = time.perf_counter() + limit_s
    while s.got.answered < expected and time.perf_counter() < end:
        time.sleep(0.005)


def run_window(s: ServeSetup, seconds: float, max_answers=None) -> ServeWindow:
    """The window's requests, then a wait of up to DRAIN_S for their answers."""
    w = offer(s, seconds, tag=2)
    wait_answers(s, DRAIN_S, expected=int((~w.shed).sum()))
    w.t_close = time.perf_counter()
    return w


def latencies_ms(s: ServeSetup, w: ServeWindow, drain_s: float) -> tuple[np.ndarray, int]:
    """Latency of every offered request from when it was due; a shed,
    failed or unanswered request gets the whole time it was given."""
    given = (w.t_end - w.t_start) + drain_s
    ok = ~w.shed & (s.got.count > 0) & ~s.got.error
    lat = np.where(ok, (s.got.t - w.due) * 1e3, given * 1e3)
    return lat, int((~ok).sum())


def compare(cell, s: ServeSetup, w: ServeWindow,
            reference_control: bool = False) -> tuple[dict, dict]:
    """(the served model's record as fit_traffic's, the serving numbers):
    every answered label against the plain reference, and every offered
    request answered exactly once or shed."""
    from .reference import nearest_table

    model, ref_params = _record(
        cell, s.config, s.seed, s.X_fit, rows_mixture, s.fitted, reference_control,
        lambda a: next_centroids(s.estimator, s.fit_store, a))
    D = nearest_table(s.X_held, cell.reference.embed, ref_params,
                      s.fitted.centroids, s.config["discrepancy"])
    dmin = D.min(axis=1)
    g = s.got
    expect = np.where(w.shed, 0, 1)
    unaccounted = int((g.count != expect).sum()) + g.stray
    judged = (g.count == 1) & ~g.error & ~w.shed
    labels = g.label[judged]
    rows = w.rows[judged]
    k = D.shape[1]
    valid = (labels >= 0) & (labels < k)
    gaps = D[rows[valid], labels[valid]] - dmin[rows[valid]]
    worst = float(gaps.max()) if gaps.size else 0.0
    if not valid.all():
        worst = float("inf")  # a label that names no centroid
    return model, {"assign_gap": worst / float(dmin.mean()),
                   "unaccounted": float(unaccounted)}


def finish(cell, s: ServeSetup, w: ServeWindow, max_compared=None,
           reference_control: bool = False) -> Outcome:
    lat, failed = latencies_ms(s, w, DRAIN_S)
    s.tier.stop()
    model, served = compare(cell, s, w, reference_control)
    late = (w.submitted - w.due) * 1e3
    extra = {"offered": int(lat.shape[0]), "shed": int(w.shed.sum()),
             "latency_ms": {"p99": quantile(lat, 0.99),
                            "p999": quantile(lat, 0.999)},
             "generator_late_ms": {"p50": quantile(late, 0.5),
                                   "p99": quantile(late, 0.99),
                                   "max": float(late.max()) if late.size else 0.0},
             "model": model, "served": served}
    return Outcome({"assign_p50_ms": quantile(lat, 0.50)}, int(lat.shape[0]),
                   failed, [model["numbers"], served], extra)


def quantile(values: np.ndarray, q: float) -> float:
    """Nearest-rank quantile: the smallest value with a share q at or below it."""
    if values.size == 0:
        return 0.0
    v = np.sort(values)
    return float(v[max(0, math.ceil(q * v.size) - 1)])
