"""Traffic kind "fit": whole fits back to back through `KernelKMeans.fit`.

Set-up makes the configuration's rows once in host RAM, in the row format
the configuration names (`rows`, default "mixture": spec.rows_module), and
holds them where the traffic says (`hold`):

  "host" (the default)  a `BlockStore` that a deployment streams from. Every
                        program a fit runs is warmed by one whole fit on a
                        small store with the same block shapes (the same
                        full-block and tail-block rows, on the same devices).
  "device"              the whole X placed in device memory once. Every fit
                        of the window gets that array; the warm-up is one
                        whole fit on it, since the resident driver's
                        programs are shaped by the whole n.

The window then runs fits, each with its own key from `--seed`, until
`--seconds` have passed. A fit still running at the deadline finishes but is
dropped.

fit_rows_per_s counts n rows for every pass a completed fit made: phase 1's
reservoir pass, each Lloyd iteration and the final assignment pass (the
fit's own `rows_seen` plus n), over the wall time from the first fit's start
to the end of the last completed fit.

After the window a sample of its fits, drawn from the seed, is held to the
plain reference. A fit's centroids must be the mean of its labels only at a
fixed point (no label changed). A fit stopped at the `iters` cap returns
centroids one update behind its labels, so its centroid update is checked by
one more: the program's own Lloyd driver, the one the fit ran, with the
fit's policy, input and compiled step, runs one iteration from the returned
centroids, and what it returns must be the mean of the returned labels.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import data, spec
from .reference import FitAnswer, fit_numbers, score_rows
from .spec import Outcome

FIT_KEY_BASE = 1000  # fit i of the window uses fold_in(base_key(seed), 1000 + i)
WARM_KEY = 999


def fit_key(seed: int, key_seed: int):
    return jax.random.fold_in(data.base_key(seed), key_seed)


def _params_to_host(params) -> dict:
    return {f.name: np.asarray(getattr(params, f.name))
            for f in dataclasses.fields(params)
            if isinstance(getattr(params, f.name), jax.Array)}


def warm_rows(n: int, block_rows: int, devices: int) -> int:
    """Rows of a small store whose blocks have the real store's shapes and
    land on the same devices: every device gets a full block, and the tail
    block (the real store's last, maybe short, block) sits on the device
    that streams the real tail."""
    nb = -(-n // block_rows)
    tail = n - (nb - 1) * block_rows
    last = devices + (nb - 1 - devices) % devices  # index of the warm tail
    if last >= nb - 1:
        return n
    return last * block_rows + tail


def estimator(cfg: dict, backend: str, policy=None, mesh=None,
              scheduler: str = "lockstep"):
    """An unfitted `KernelKMeans` as the configuration states it."""
    from repro.api import KernelKMeans

    kw = {} if policy is None else {"policy": policy}
    if mesh is not None:
        kw.update(mesh=mesh, scheduler=scheduler)
    return KernelKMeans(
        cfg["k"], kernel=cfg["kernel"],
        kernel_params={"gamma": cfg["gamma_times_d"] / cfg["d"]},
        method=cfg["method"], backend=backend, l=cfg["l"], m=cfg["m"],
        iters=cfg["iters"], n_init=cfg["n_init"],
        block_rows=cfg["block_rows"], landmark_sample=cfg["landmark_sample"],
        seed_sample=cfg["seed_sample"], **kw,
    )


def answer(est, index: int, key_seed: int) -> FitAnswer:
    """What a finished fit returned, copied to the host (the fitted model
    is kept as it is, for `next_centroids`)."""
    return FitAnswer(
        index=index, key_seed=key_seed,
        params=_params_to_host(est.model_.params),
        centroids=np.asarray(est.model_.centroids),
        labels=est.labels_, inertia=float(est.inertia_),
        n_iter=int(est.n_iter_), iters_cap=int(est.iters), model=est.model_,
    )


def next_centroids(template, fit_input, a: FitAnswer) -> np.ndarray:
    """The program's next Lloyd update of a fit's returned state: one
    iteration, from the returned centroids, of the driver the fit ran, as
    `template` (an estimator made as the fit's was) runs it on `fit_input`
    (what the fit was given)."""
    if a.model.meta.backend == "local":
        return _next_centroids_local(template, fit_input, a)
    from repro.stream.lloyd import ooc_lloyd

    kw = {}
    if template.mesh is not None:
        from repro.stream.sharded import shard_devices

        kw = {"devices": shard_devices(template.mesh),
              "scheduler": template.scheduler}
    res = ooc_lloyd(fit_input, template.k, coeffs=a.model.params, iters=1,
                    init=a.model.centroids, policy=template.policy, **kw)
    return np.asarray(res.centroids)


def _next_centroids_local(template, X, a: FitAnswer) -> np.ndarray:
    """The local driver's update: one `core.lloyd.lloyd` iteration over the
    program's own embedding of X under the fit's parameters and policy."""
    from repro import embed
    from repro.core.lloyd import lloyd

    params = a.model.params
    Y = embed.transform(params, jnp.asarray(X, jnp.float32), template.policy)
    res = lloyd(Y, template.k, discrepancy=params.discrepancy, iters=1,
                init=a.model.centroids, policy=template.policy)
    return np.asarray(res.centroids)


@dataclasses.dataclass
class FitSetup:
    config: dict
    traffic: dict
    seed: int
    rows: object  # the configuration's rows, in its row format
    fmt: object  # the row format module (spec.rows_module)
    fit_input: object  # what every fit of the window is given
    make_estimator: object  # () -> KernelKMeans
    devices: list


def setup(cell, seed: int, policy=None) -> FitSetup:
    cfg, tr = cell.config, cell.traffic
    fmt = spec.rows_module(cfg.get("rows", "mixture"))
    hold = tr.get("hold", "host")
    rows = fmt.make(seed, cfg, cfg["n"], data.STREAM_FIT)
    devices = jax.devices()[: cell.chips]
    fit_input = fmt.program_input(rows, cfg["block_rows"], hold, devices)
    mesh = None
    if tr["backend"] == "stream_shard":
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(devices).reshape(len(devices), 1), ("data", "model"))

    def make_estimator():
        return estimator(cfg, tr["backend"], policy, mesh,
                         tr.get("scheduler", "lockstep"))

    if hold == "host":
        # warm every program on a small store with the same block shapes
        n_warm = warm_rows(cfg["n"], cfg["block_rows"], len(devices))
        warm_input = fmt.program_input(fmt.head(rows, n_warm), cfg["block_rows"],
                                       hold, devices)
    else:
        warm_input = fit_input
    make_estimator().fit(warm_input, key=fit_key(seed, WARM_KEY))
    return FitSetup(cfg, tr, seed, rows, fmt, fit_input, make_estimator, devices)


@dataclasses.dataclass
class FitWindow:
    answers: list  # FitAnswer of every fit completed in the window
    rows: int  # rows counted over the completed fits
    span_s: float  # first fit's start -> end of the last completed fit
    window_s: float  # wall time of the whole loop, the dropped fit included
    t_start: float
    t_end: float
    phases: list  # FitReport.phases of each completed fit
    fit_s: list  # wall seconds of each completed fit
    dropped: int
    started: int  # fits started, the dropped one included

    @property
    def t_close(self) -> float:
        return self.t_end


def run_window(s: FitSetup, seconds: float, max_fits: int | None = None) -> FitWindow:
    """Fits until `seconds` pass (or, for tests, `max_fits` have completed)."""
    answers, phases, fit_s = [], [], []
    rows = 0
    t_start = time.perf_counter()
    deadline = t_start + seconds
    t_last = t_start
    dropped = 0
    i = 0
    while time.perf_counter() < deadline:
        key_seed = FIT_KEY_BASE + i
        t0 = time.perf_counter()
        est = s.make_estimator().fit(s.fit_input, key=fit_key(s.seed, key_seed))
        t1 = time.perf_counter()
        i += 1
        if t1 > deadline:
            dropped += 1
            break
        t_last = t1
        rep = est.fit_report_
        rows += int(rep.rows_seen) + s.config["n"]  # + phase 1's reservoir pass
        phases.append(dict(rep.phases))
        fit_s.append(t1 - t0)
        answers.append(answer(est, len(answers), key_seed))
        if max_fits is not None and len(answers) >= max_fits:
            break
    t_end = time.perf_counter()
    return FitWindow(answers, rows, t_last - t_start, t_end - t_start,
                     t_start, t_end, phases, fit_s, dropped, i)


def compare(cell, s: FitSetup, window: FitWindow, max_fits: int,
            reference_control: bool = False) -> tuple[list, list]:
    """Hold a sample of the window's fits, drawn from the seed, to the plain
    reference. Returns (per-fit records, the numbers of each).

    `reference_control` also reads the parameter checks of the reference's
    own one-precision-lower parameters put in the fit's place (the upper
    readings of those checks); only limit-setting runs ask for it."""
    answers = window.answers
    rng = np.random.default_rng([s.seed, 7])
    pick = rng.choice(len(answers), size=min(max_fits, len(answers)),
                      replace=False).tolist() if answers else []
    template = s.make_estimator()
    records = [_record(cell, s.config, s.seed, s.rows, s.fmt, answers[j],
                       reference_control,
                       lambda a: next_centroids(template, s.fit_input, a))[0]
               for j in sorted(pick)]
    return records, [r["numbers"] for r in records]


def _record(cell, config: dict, seed: int, rows, fmt, a: FitAnswer,
            reference_control: bool, step) -> tuple[dict, object]:
    """(the numbers of one fit against the reference on its rows, held in
    row format `fmt`, the parameters the reference embedded with). `step(a)`
    gives the program's next update of a fit that stopped at the cap."""
    key = fit_key(seed, a.key_seed)
    ref_params, checks = cell.reference.reference_params(config, key, a.params, rows)
    scored = score_rows(fmt.ref_chunks(rows), fmt.REF_CHUNK_ROWS,
                        cell.reference.embed, ref_params, a.centroids, a.labels,
                        config["discrepancy"])
    nums = dict(checks)
    nums.update(fit_numbers(a, scored, None if a.converged else step(a)))
    rec = {"fit": a.index, "n_iter": a.n_iter, "converged": a.converged,
           "numbers": nums}
    if reference_control:
        low = cell.reference.control_params(config, key, a.params)
        rec["reference_control"] = cell.reference.reference_params(
            config, key, low, rows)[1]
    return rec, ref_params


def finish(cell, s: FitSetup, window: FitWindow, max_fits: int | None,
           reference_control: bool) -> Outcome:
    records, numbers = compare(cell, s, window,
                               max_fits or s.traffic["compared_fits"],
                               reference_control)
    rate = window.rows / window.span_s if window.answers else None
    extra = {"fits": [{"n_iter": a.n_iter, "seconds": t}
                      for a, t in zip(window.answers, window.fit_s)],
             "dropped_fits": window.dropped, "compared": records}
    return Outcome({"fit_rows_per_s": rate}, len(window.answers), 0, numbers, extra)
