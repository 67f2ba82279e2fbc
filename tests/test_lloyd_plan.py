"""The fused Lloyd-step plan (DESIGN.md §16): fused-vs-reference equivalence
across every registered embedding member and policy, final-pass collapse onto
the plan, the s-step sharded variant, and the deprecation shims."""
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernels_fn import Kernel
from repro.core.lloyd import assign_stats, block_cost
from repro.embed import available_embeddings, get_embedding
from repro.kernels import ops
from repro.policy import ComputePolicy

K = 5


def _member_kernel(name: str) -> Kernel:
    fams = getattr(get_embedding(name), "kernel_families", None)
    if fams is not None and "rbf" not in fams:
        return Kernel(fams[0], degree=2, coef0=1.0) if fams[0] == "poly" \
            else Kernel(fams[0])
    return Kernel("rbf", gamma=0.3)


def _fit_member(name: str, X):
    emb = get_embedding(name)
    return emb.fit(jax.random.PRNGKey(7), X, _member_kernel(name), l=24, m=12)


@pytest.fixture(scope="module")
def block():
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (300, 6))
    return X + jnp.where(jnp.arange(300)[:, None] < 150, 3.0, 0.0)


POLICIES = [
    ComputePolicy(pallas=False),
    ComputePolicy(pallas=True),
    ComputePolicy(pallas=False, precision="bf16"),
    ComputePolicy(pallas=True, precision="bf16"),
]


@pytest.mark.parametrize("name", available_embeddings())
@pytest.mark.parametrize("pol", POLICIES, ids=lambda p: f"pallas={p.pallas}-{p.precision}")
def test_plan_matches_unfused_chain(block, name, pol):
    """Satellite: the plan's (Z, g, labels, cost) match the un-fused
    embed_block_map + assign_stats + block_cost chain within tolerance for
    every member x policy, with exact label identity at f32."""
    params = _fit_member(name, block)
    plan = ops.lloyd_step_plan(params=params, policy=pol)

    Y = ops.embed_block_map(block, params, policy=pol)
    C = Y[:K]
    Zr, gr, lr = assign_stats(Y, C, K, params.discrepancy, policy=pol)
    costr = block_cost(Y, C, params.discrepancy)

    Z, g, labels, cost = plan.step(block, C)
    assert labels.dtype == jnp.int32 and labels.shape == lr.shape
    if pol.precision == "f32":
        np.testing.assert_array_equal(np.asarray(labels), np.asarray(lr))
    else:  # bf16 leaf-cast path: near-ties may flip — require high agreement
        assert float(jnp.mean(labels == lr)) > 0.98
    tol = 1e-4 if pol.precision == "f32" else 5e-2
    np.testing.assert_allclose(np.asarray(Z), np.asarray(Zr), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=tol, atol=tol)
    np.testing.assert_allclose(float(cost), float(costr), rtol=tol)

    la, ca = plan.assign(block, C)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(labels))
    np.testing.assert_allclose(float(ca), float(cost), rtol=1e-6)


@pytest.mark.parametrize("name", available_embeddings())
def test_plan_y_mode_matches_assign_chain(block, name):
    """Y-mode plan (embedded blocks: local backend, sweep cache) reproduces
    assign_stats + block_cost: identical labels, and (Z, g, cost) equal up
    to f32 summation order."""
    params = _fit_member(name, block)
    pol = ComputePolicy(pallas=False)
    Y = ops.embed_block_map(block, params, policy=pol)
    C = Y[:K]
    plan = ops.lloyd_step_plan(discrepancy=params.discrepancy, policy=pol)
    Z, g, labels, cost = plan.step(Y, C)
    Zr, gr, lr = assign_stats(Y, C, K, params.discrepancy, policy=pol)
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(lr))

    # The plan jits the one-hot matmul and the cost reduction into one
    # program, so XLA may sum the same f32 terms in another order. Each entry
    # is a sum of at most n=300 terms: the reordering error is a few ulps of
    # the largest entry (measured: at most 4e-7 of it), far below 1e-5 of it.
    def close(a, b):
        b = np.asarray(b)
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=1e-5, atol=1e-5 * float(np.abs(b).max())
        )

    close(Z, Zr)
    close(g, gr)
    close(cost, block_cost(Y, C, params.discrepancy))


def test_fused_members_fuse_and_tensorsketch_falls_back(block):
    """Routing: Pallas policy fuses APNC q=1 and RFF; TensorSketch (FFT) and
    q>1 APNC fall back to the un-fused chain; non-Pallas never fuses."""
    pol = ComputePolicy(pallas=True)
    for name, fused in [("nystrom", True), ("sd", True), ("rff", True),
                        ("tensorsketch", False)]:
        params = _fit_member(name, block)
        assert ops.lloyd_step_plan(params=params, policy=pol).fused is fused
        assert not ops.lloyd_step_plan(
            params=params, policy=ComputePolicy(pallas=False)).fused
    q2 = get_embedding("nystrom").fit(
        jax.random.PRNGKey(7), block, Kernel("rbf", gamma=0.3), l=16, m=8, q=2
    )
    assert not ops.lloyd_step_plan(params=q2, policy=pol).fused
    with pytest.raises(ValueError):
        ops.fused_lloyd_step(block, q2, jnp.zeros((K, 16)))


def test_y_mode_requires_discrepancy():
    with pytest.raises(ValueError, match="discrepancy"):
        ops.lloyd_step_plan()


@pytest.mark.parametrize("name", available_embeddings())
def test_final_assign_matches_pre_refactor_chain(name):
    """Satellite: the collapsed final pass (stream + sharded now share the
    plan's assign) keeps label identity with the pre-refactor hand-rolled
    embed-once chain, for every registered member."""
    from repro.stream.blockstore import BlockStore
    from repro.stream.lloyd import ooc_lloyd

    X = np.random.default_rng(3).normal(size=(800, 5)).astype(np.float32)
    X[:400] += 4.0
    store = BlockStore.from_array(X, block_rows=128)
    params = _fit_member(name, jnp.asarray(X[:300]))
    pol = ComputePolicy(pallas=False)
    res = ooc_lloyd(store, 3, coeffs=params, key=jax.random.PRNGKey(0),
                    iters=5, policy=pol)

    # the pre-refactor final pass, hand-rolled: embed once, reuse Y
    want = np.empty(store.n, np.int32)
    inertia = 0.0
    for i in range(store.num_blocks):
        x = jnp.asarray(store.get(i))
        y = ops.embed_block_map(x, params, policy=pol)
        _, _, lab = assign_stats(y, res.centroids, 3, params.discrepancy,
                                 policy=pol)
        lo = store.row_offset(i)
        want[lo:lo + lab.shape[0]] = np.asarray(lab, np.int32)
        inertia += float(block_cost(y, res.centroids, params.discrepancy))
    np.testing.assert_array_equal(res.labels, want)
    np.testing.assert_allclose(res.inertia, inertia, rtol=1e-5)


def test_fused_dispatch_counter_and_span(block):
    """The plan's engine maps tick engine.fused_dispatches and emit the
    lloyd.fused_step span when (and only when) the step actually fused."""
    from repro import obs

    params = _fit_member("rff", block)
    before = obs.snapshot("engine.").get("engine.fused_dispatches", 0)
    plan = ops.lloyd_step_plan(params=params, policy=ComputePolicy(pallas=True))
    Y = ops.embed_block_map(block, params, policy=ComputePolicy(pallas=False))
    fn = plan.block_map([Y[:K]])
    fn(block)
    assert obs.snapshot("engine.")["engine.fused_dispatches"] == before + 1
    unfused = ops.lloyd_step_plan(params=params, policy=ComputePolicy(pallas=False))
    unfused.block_map([Y[:K]])(block)
    assert obs.snapshot("engine.")["engine.fused_dispatches"] == before + 1


def test_sstep_policy_validation():
    assert ComputePolicy().sstep == 1
    assert ComputePolicy(sstep=4).sstep == 4
    with pytest.raises(ValueError, match="sstep"):
        ComputePolicy(sstep=0)
    with pytest.raises(ValueError, match="sstep"):
        ComputePolicy(sstep=-2)


def test_sstep_single_device_is_exact():
    """On one device, local stats ARE global: sstep > 1 must be a no-op."""
    from repro.stream.blockstore import BlockStore
    from repro.stream.lloyd import ooc_lloyd

    X = np.random.default_rng(5).normal(size=(900, 6)).astype(np.float32)
    X[:450] += 4.0
    store = BlockStore.from_array(X, block_rows=128)
    params = _fit_member("rff", jnp.asarray(X[:300]))
    devs = [jax.local_devices()[0]]
    r1 = ooc_lloyd(store, 3, coeffs=params, key=jax.random.PRNGKey(0),
                   iters=6, devices=devs, policy=ComputePolicy(sstep=1))
    r3 = ooc_lloyd(store, 3, coeffs=params, key=jax.random.PRNGKey(0),
                   iters=6, devices=devs, policy=ComputePolicy(sstep=3))
    np.testing.assert_array_equal(r1.labels, r3.labels)
    assert r1.inertia == r3.inertia


def test_sstep_multi_device_agreement_subprocess():
    """On a forced 8-device mesh, sstep=3 reaches label/inertia agreement
    with sstep=1 (the final pass always runs under synced centroids)."""
    code = """
import jax, numpy as np, jax.numpy as jnp
from repro.policy import ComputePolicy
from repro.stream.blockstore import BlockStore
from repro.stream.lloyd import ooc_lloyd
from repro.embed import get_embedding
from repro.core.kernels_fn import Kernel

X = np.random.default_rng(0).normal(size=(6000, 8)).astype(np.float32)
X[:3000] += 6.0
store = BlockStore.from_array(X, block_rows=512)
params = get_embedding("rff").fit(jax.random.PRNGKey(1), jnp.asarray(X[:1000]),
                                  Kernel("rbf", gamma=0.2), l=32, m=32)
devs = jax.local_devices()
assert len(devs) == 8
key = jax.random.PRNGKey(0)
r1 = ooc_lloyd(store, 2, coeffs=params, key=key, devices=devs,
               policy=ComputePolicy(sstep=1), iters=8)
rs = ooc_lloyd(store, 2, coeffs=params, key=key, devices=devs,
               policy=ComputePolicy(sstep=3), iters=8)
agree = float(np.mean(r1.labels == rs.labels))
rel = abs(r1.inertia - rs.inertia) / max(r1.inertia, 1e-9)
assert agree >= 0.95, agree
assert rel <= 0.02, rel
print("OK", agree, rel)
"""
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src")}
    import os
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, **env},
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_deprecated_shims_warn_and_stay_bit_exact(block):
    """Satellite: core.nystrom.fit / core.stable.fit and the ops.apnc_*
    aliases warn with DeprecationWarning naming the replacement and delegate
    bit-exactly."""
    from repro.core import nystrom, stable
    from repro.embed.apnc import fit_nystrom, fit_sd

    key = jax.random.PRNGKey(2)
    kern = Kernel("rbf", gamma=0.3)
    with pytest.deprecated_call(match="fit_nystrom"):
        a = nystrom.fit(key, block, kern, l=16, m=8)
    b = fit_nystrom(key, block, kern, l=16, m=8)
    np.testing.assert_array_equal(np.asarray(a.R), np.asarray(b.R))
    np.testing.assert_array_equal(np.asarray(a.landmarks), np.asarray(b.landmarks))

    with pytest.deprecated_call(match="fit_sd"):
        a = stable.fit(key, block, kern, l=16, m=8)
    b = fit_sd(key, block, kern, l=16, m=8)
    np.testing.assert_array_equal(np.asarray(a.R), np.asarray(b.R))

    params = _fit_member("nystrom", block)
    with pytest.deprecated_call(match="embed_block_map"):
        ya = ops.apnc_embed_block_map(block, params)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        yb = ops.embed_block_map(block, params)
    np.testing.assert_array_equal(np.asarray(ya), np.asarray(yb))
    C = yb[:K]
    with pytest.deprecated_call(match="embed_assign_block"):
        Za, ga, la = ops.apnc_embed_assign_block(block, params, C)
    Zb, gb, lb = ops.embed_assign_block(block, params, C)
    np.testing.assert_array_equal(np.asarray(Za), np.asarray(Zb))
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    with pytest.deprecated_call(match="predict_block"):
        pa = ops.apnc_predict_block(block, params, C)
    np.testing.assert_array_equal(
        np.asarray(pa), np.asarray(ops.predict_block(block, params, C)))


def test_lloyd_step_roofline_record():
    """The fused-step roofline record: fused strictly cheaper in HBM bytes
    (by exactly the Y round-trip), equal flops, and joinable to a
    model_fraction."""
    from repro import obs
    from repro.roofline.analysis import lloyd_step_record

    fused = lloyd_step_record(n=4096, d=16, l=256, m=128, k=8)
    unfused = lloyd_step_record(n=4096, d=16, l=256, m=128, k=8, fused=False)
    assert fused["flops"] == unfused["flops"]
    assert unfused["hbm_bytes"] - fused["hbm_bytes"] == 2 * 4 * 4096 * 128
    joined = obs.roofline_join(1e-3, fused)
    assert 0.0 < joined["model_fraction"] < 1.0
