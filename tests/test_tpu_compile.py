"""Ahead-of-time compiles of the clustering kernels for a TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a described
`v5e:2x2` topology. Nothing runs, so these tests check only what the chip's
compiler would refuse (an op Mosaic cannot lower, a misaligned slice, too
much VMEM) at the paper's widths: ImageNet (d=900, l=300, m=200, k=164) for
the APNC kernels, CovType (d=54, k=7) for RFF.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file. Keep all of these compiles in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.apnc import APNCCoefficients
from repro.core.kernels_fn import Kernel
from repro.embed.rff import RFFParams
from repro.kernels import ops

# ImageNet row of the paper's Table 1, with the estimator's default l and m.
N, D, L, M, K = 4096, 900, 300, 200, 164
RBF = Kernel("rbf", gamma=1.0 / D)
# CovType row: d=54, k=7; m=200 random features -> a 400-wide embedding.
COV_D, COV_K, RFF_M = 54, 7, 200


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _apnc(disc):
    def f(x, lm, r, c):
        params = APNCCoefficients(lm[None], r[None], RBF, disc)
        return ops.fused_lloyd_step(x, params, c, interpret=False)

    return f, [(N, D), (L, D), (M, L), (K, M)]


def _rff_step():
    def f(x, w, c):
        return ops.fused_lloyd_step(x, RFFParams(w, RBF), c, interpret=False)

    return f, [(N, COV_D), (COV_D, RFF_M), (COV_K, 2 * RFF_M)]


def _dequant(dtype):
    # ragged n: the padded wrapper masks the 96 tail rows of the last tile
    def f(yq, scale, c):
        return ops._dequant_step_padded(
            yq, scale, c, "l2", ops._lloyd_step.DEFAULT_BN, False
        )

    return f, [((4000, M), dtype), (1, M), (K, M)]


def _embed():
    def f(x, lm, r):
        return ops.apnc_embed(x, APNCCoefficients(lm[None], r[None], RBF, "l2"),
                              interpret=False)

    return f, [(N, D), (L, D), (M, L)]


def _assign(disc):
    def f(y, c):
        return ops.apnc_assign(y, c, disc, interpret=False)

    return f, [(N, M), (K, M)]


def _rff_embed():
    def f(x, w):
        return ops.rff_embed(x, RFFParams(w, RBF), interpret=False)

    return f, [(N, COV_D), (COV_D, RFF_M)]


CASES = {
    "fused_apnc_step_l2": lambda: _apnc("l2"),
    "fused_apnc_step_l1": lambda: _apnc("l1"),
    "fused_rff_step": _rff_step,
    "dequant_step_int8": lambda: _dequant(jnp.int8),
    "dequant_step_bf16": lambda: _dequant(jnp.bfloat16),
    "apnc_embed": _embed,
    "apnc_assign_l2": lambda: _assign("l2"),
    "apnc_assign_l1": lambda: _assign("l1"),
    "rff_embed": _rff_embed,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, specs = CASES[case]()
    args = [
        jax.ShapeDtypeStruct(*(s if isinstance(s[0], tuple) else (s, jnp.float32)),
                             sharding=one_chip)
        for s in specs
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
