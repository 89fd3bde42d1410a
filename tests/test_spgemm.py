"""SpGEMM: paper-primitive emulation, Pallas kernel sweeps, skip models."""
import numpy as np
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import spgemm as sg
from repro.core import stats
from repro.kernels.bitmap_spgemm import (bitmap_spgemm,
                                         bitmap_spgemm_kcondensed,
                                         kcondense, plan_slices)
from repro.kernels.ref import spgemm_ref
from tests.conftest import sparse_matrix


def test_outer_step_and_merge_match_matmul(rng):
    a = sparse_matrix(rng, (32, 8), 0.5)
    b = sparse_matrix(rng, (8, 32), 0.5)
    out = sg.spgemm_emulate(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n,bm_,bn,sk", [
    (64, 128, 64, 32, 32, 32),
    (128, 256, 96, 64, 32, 64),
    (56, 120, 40, 32, 32, 32),      # unaligned
    (8, 32, 8, 8, 8, 8),
])
@pytest.mark.parametrize("da", [0.0, 0.5, 1.0])
def test_kernel_matches_ref(rng, m, k, n, bm_, bn, sk, da):
    a = sparse_matrix(rng, (m, k), 1 - da)
    b = sparse_matrix(rng, (k, n), 0.5)
    out = bitmap_spgemm(jnp.asarray(a), jnp.asarray(b), block_m=bm_,
                        block_n=bn, slice_k=sk, interpret=True)
    ref = spgemm_ref(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(rng, dtype):
    a = jnp.asarray(sparse_matrix(rng, (64, 64), 0.4)).astype(dtype)
    b = jnp.asarray(sparse_matrix(rng, (64, 64), 0.4)).astype(dtype)
    out = bitmap_spgemm(a, b, block_m=32, block_n=32, slice_k=32,
                        interpret=True)
    ref = spgemm_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_block_skip_actually_skips(rng):
    # block-structured sparsity: zero block rows of A
    a = sparse_matrix(rng, (128, 128), 0.9)
    a[:64] = 0
    b = sparse_matrix(rng, (128, 128), 0.9)
    ks, counts = plan_slices(jnp.asarray(a), jnp.asarray(b), 64, 64, 32)
    c = np.asarray(counts)
    assert (c[0] == 0).all() and (c[1] > 0).all()
    out = bitmap_spgemm(jnp.asarray(a), jnp.asarray(b), block_m=64,
                        block_n=64, slice_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)


def test_kcondense_exactness(rng):
    a = sparse_matrix(rng, (64, 256), 0.8)
    a[:, rng.random(256) < 0.5] = 0          # dead input features
    b = sparse_matrix(rng, (256, 64), 0.8)
    b[rng.random(256) < 0.3, :] = 0          # pruned input channels
    ac, bc, nact = kcondense(jnp.asarray(a), jnp.asarray(b))
    assert int(nact) < 256
    np.testing.assert_allclose(
        np.asarray(ac @ bc), a @ b, rtol=1e-4, atol=1e-4)
    out = bitmap_spgemm_kcondensed(
        jnp.asarray(a), jnp.asarray(b), block_m=32, block_n=32,
        slice_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       da=st.floats(0.0, 1.0), db=st.floats(0.0, 1.0))
def test_property_kernel_any_density(seed, da, db):
    rng = np.random.default_rng(seed)
    a = sparse_matrix(rng, (32, 64), da)
    b = sparse_matrix(rng, (64, 32), db)
    out = bitmap_spgemm(jnp.asarray(a), jnp.asarray(b), block_m=16,
                        block_n=16, slice_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# step-count models (paper Fig. 5 arithmetic)
# ---------------------------------------------------------------------------

def test_ohmma_dense_counts():
    a = np.ones((32, 1), np.float32)
    b = np.ones((1, 32), np.float32)
    sc = stats.ohmma_steps(jnp.asarray(a), jnp.asarray(b))
    assert int(sc.dense) == 8 and int(sc.sparse) == 8  # 4×2 OHMMAs


def test_ohmma_fig5_example(rng):
    # paper Fig. 5: 20/32 nnz in the A column, 11/32 in the B row
    # → ceil(20/8)·ceil(11/16) = 3 OHMMAs of 8 ⇒ 8/3 speedup
    a = np.zeros((32, 1), np.float32)
    a[rng.permutation(32)[:20], 0] = 1.0
    b = np.zeros((1, 32), np.float32)
    b[0, rng.permutation(32)[:11]] = 1.0
    sc = stats.ohmma_steps(jnp.asarray(a), jnp.asarray(b))
    assert int(sc.sparse) == 3
    np.testing.assert_allclose(float(sc.speedup), 8 / 3, rtol=1e-6)


def test_ohmma_quantisation_levels(rng):
    # A-side skip quantises to <0,25,50,75>% (ceil(ca/8) ∈ 0..4)
    for ca, expect in [(0, 0), (1, 1), (8, 1), (9, 2), (24, 3), (25, 4)]:
        a = np.zeros((32, 1), np.float32)
        a[:ca, 0] = 1.0
        b = np.ones((1, 32), np.float32)
        sc = stats.ohmma_steps(jnp.asarray(a), jnp.asarray(b))
        assert int(sc.sparse) == expect * 2, (ca, int(sc.sparse))


def test_mxu_steps_block_structured(rng):
    a = np.ones((64, 128), np.float32)
    a[:, 64:] = 0  # half the k-slices dead
    b = np.ones((128, 64), np.float32)
    sc = stats.mxu_steps(jnp.asarray(a), jnp.asarray(b), 64, 64, 64, 32)
    assert int(sc.dense) == 4 and int(sc.sparse) == 2


def test_spgemm_wrapper_stats(rng):
    a = sparse_matrix(rng, (64, 64), 0.5)
    b = sparse_matrix(rng, (64, 64), 0.5)
    res = sg.spgemm(jnp.asarray(a), jnp.asarray(b), block_m=32, block_n=32,
                    use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(res.out), a @ b, rtol=1e-4,
                               atol=1e-4)
    assert int(res.steps.dense) >= int(res.steps.sparse) > 0


@pytest.mark.parametrize("kernel", ["slice", "kfused", "grouped",
                                    "grouped_kfused"])
def test_kernels_split_schedules_that_overflow_smem(rng, monkeypatch,
                                                    kernel):
    """A schedule too big for one call's SMEM runs as several calls over
    sub-rectangles of the output grid (or expert ranges) — same result."""
    from repro.kernels import bitmap_spgemm as bsk
    from repro.kernels import grouped_spgemm as gsk
    monkeypatch.setattr(bsk, "SMEM_SCHEDULE_WORDS", 16)
    a = sparse_matrix(rng, (40, 300), 0.5)
    b = sparse_matrix(rng, (300, 50), 0.5)
    kw = dict(block_m=8, block_n=16, slice_k=32, interpret=True)
    if kernel.startswith("grouped"):
        a, b = np.stack([a[:, :64]] * 3), np.stack([b[:64]] * 3)
        fn = (gsk.grouped_spgemm if kernel == "grouped"
              else gsk.grouped_spgemm_kfused)
        want = np.einsum("eck,ekn->ecn", a, b)
    else:
        fn = (bsk.bitmap_spgemm if kernel == "slice"
              else bsk.bitmap_spgemm_kfused)
        want = a @ b
    out = fn(jnp.asarray(a), jnp.asarray(b), **kw)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
