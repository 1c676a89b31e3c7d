"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Property-style sweeps are seeded pytest.mark.parametrize grids (no hypothesis
dependency): each case derives (shape, data) deterministically from its seed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fwht, ops, ref, sparse_assign

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("p", [64, 128, 256, 512, 2048, 8192])
@pytest.mark.parametrize("n", [1, 16, 37])
def test_fwht_kernel_shapes(p, n):
    x = jax.random.normal(KEY, (n, p), jnp.float32)
    s = jax.random.rademacher(jax.random.PRNGKey(1), (p,), jnp.float32)
    y = fwht.hd_precondition(x, s, interpret=True)
    np.testing.assert_allclose(y, ref.ref_hd_precondition(x, s), atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_kernel_dtypes(dtype):
    p, n = 512, 9
    x = jax.random.normal(KEY, (n, p)).astype(dtype)
    s = jax.random.rademacher(jax.random.PRNGKey(1), (p,), jnp.float32).astype(dtype)
    y = fwht.hd_precondition(x, s, interpret=True)
    r = ref.ref_hd_precondition(x.astype(jnp.float32), s.astype(jnp.float32))
    tol = 2e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(y.astype(jnp.float32), r, atol=tol)


def test_fwht_kernel_rejects_non_pow2():
    with pytest.raises(ValueError):
        fwht.factor_p(100)


@pytest.mark.parametrize("shape", [(33, 256, 16, 5), (64, 1024, 64, 10), (17, 512, 128, 3), (5, 128, 2, 2)])
def test_sparse_assign_kernel_shapes(shape):
    n, p, m, k = shape
    kv, ki, kc = jax.random.split(jax.random.PRNGKey(n), 3)
    vals = jax.random.normal(kv, (n, m), jnp.float32)
    u = jax.random.uniform(ki, (n, p))
    idx = jnp.sort(jax.lax.top_k(u, m)[1].astype(jnp.int32), axis=-1)
    ctr = jax.random.normal(kc, (k, p), jnp.float32)
    d, a = sparse_assign.sparse_assign(vals, idx, ctr, interpret=True)
    dr, ar = ref.ref_sparse_assign(vals, idx, ctr)
    np.testing.assert_allclose(d, dr, atol=1e-3)
    assert bool(jnp.all(a == ar))


@pytest.mark.parametrize("seed", range(10))
def test_property_fwht_kernel_random(seed):
    """Seeded sweep over random (p, n): kernel == butterfly oracle."""
    rng = np.random.default_rng(seed)
    p = 1 << int(rng.integers(6, 12))
    n = int(rng.integers(1, 25))
    key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
    x = jax.random.normal(key, (n, p), jnp.float32)
    s = jax.random.rademacher(jax.random.fold_in(key, 1), (p,), jnp.float32)
    y = fwht.hd_precondition(x, s, interpret=True)
    np.testing.assert_allclose(y, ref.ref_hd_precondition(x, s), atol=2e-4)


def test_ops_wrappers_dispatch():
    x = jax.random.normal(KEY, (8, 256), jnp.float32)
    s = jax.random.rademacher(jax.random.PRNGKey(1), (256,), jnp.float32)
    np.testing.assert_allclose(
        ops.hd_precondition(x, s, mode="interpret"),
        ops.hd_precondition(x, s, mode="ref"),
        atol=2e-4,
    )
    vals = jax.random.normal(KEY, (8, 16), jnp.float32)
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(KEY, (8, 256)), 16)[1].astype(jnp.int32), axis=-1)
    ctr = jax.random.normal(KEY, (4, 256), jnp.float32)
    d1, a1 = ops.sparse_assign(vals, idx, ctr, mode="interpret")
    d2, a2 = ops.sparse_assign(vals, idx, ctr, mode="ref")
    np.testing.assert_allclose(d1, d2, atol=1e-3)
    assert bool(jnp.all(a1 == a2))


def test_kernel_assign_fn_in_lloyd():
    """The kernel adapter slots into the Lloyd loop and matches the ref path."""
    from repro.core import kmeans as km

    n, p, m, k = 60, 128, 16, 3
    kv, ki = jax.random.split(KEY)
    vals = jax.random.normal(kv, (n, m), jnp.float32)
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(ki, (n, p)), m)[1].astype(jnp.int32), axis=-1)
    mu_ref, a_ref, o_ref, _ = km.sparse_kmeans_core(vals, idx, p, k, KEY, n_init=2, max_iter=10)
    fn = __import__("repro.kernels.ops", fromlist=["kernel_assign_fn"]).kernel_assign_fn("ref")
    mu_k, a_k, o_k, _ = km.sparse_kmeans_core(vals, idx, p, k, KEY, n_init=2, max_iter=10, assign_fn=fn)
    np.testing.assert_allclose(mu_ref, mu_k, atol=1e-4)
    assert bool(jnp.all(a_ref == a_k))


# ----------------------------- tiled spmm: VMEM planning + dtype handling ---
# The spmm kernels tile BOTH grid axes (kernels/spmm.py), so plan_tiles must
# find a (block_rows, block_cols) pair fitting the ONE budget at any p — the
# old "fall back to jnp past ~2^15" ceiling is gone, and ops._sparse_mode
# sizes the footprint at the ACTUAL operand dtypes (the old gate hard-coded
# 4-byte items and disagreed with the planner's own budget).

_SPMM_BUDGET = ops._SPMM_VMEM_BUDGET


def _spmm_vmem(p, ell, value_dtype=jnp.float32, dense_dtype=jnp.float32):
    from repro.kernels import spmm as spmm_mod

    br, pb = spmm_mod.plan_tiles(p, ell, value_dtype, dense_dtype)
    return spmm_mod.tile_vmem_bytes(p, ell, value_dtype, dense_dtype, br, pb)


@pytest.mark.parametrize("p", [4096, 8192, 16384, 32768, 1 << 16, 1 << 20])
@pytest.mark.parametrize("dtypes", [
    (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.bfloat16),
    (jnp.bfloat16, jnp.float32),
])
def test_sparse_mode_keeps_kernel_at_any_p_l128(p, dtypes):
    """The l=128 regime across dtypes: the planned tiles always fit the
    budget (column blocks shrink instead of falling back), so the gate keeps
    the kernel at every p — including the old jnp-fallback sizes 2^14..2^20."""
    vd, dd = dtypes
    assert _spmm_vmem(p, 128, vd, dd) <= _SPMM_BUDGET
    assert ops._sparse_mode("kernel", p, 128, vd, dd) == "kernel"


def test_sparse_mode_gate_agrees_with_planner():
    """The dispatch gate and the tile planner share ONE footprint model: the
    gate's decision must equal the planner's own fits-the-budget check,
    dtype by dtype (this is the single-sourcing the old gate lacked)."""
    for p, ell in [(8192, 256), (1 << 16, 128), (4096, 512)]:
        for vd, dd in [(jnp.float32, jnp.float32), (jnp.bfloat16, jnp.float32),
                       (jnp.float64, jnp.float64)]:
            fits = _spmm_vmem(p, ell, vd, dd) <= _SPMM_BUDGET
            assert (ops._sparse_mode("kernel", p, ell, vd, dd) == "kernel") == fits


def test_sparse_mode_vocabulary_and_interpret():
    """"auto" resolves by backend (ref on CPU); Plan.impl spellings like "jnp"
    normalize to ref instead of reaching a Pallas compile; "interpret" is
    exempt from the VMEM budget (host interpreter has no VMEM)."""
    assert ops._sparse_mode("auto", 1 << 20, 128) == "ref"      # CPU CI host
    assert ops._sparse_mode("jnp", 256, 8) == "ref"
    assert ops._sparse_mode("ref", 256, 8) == "ref"
    assert ops._sparse_mode("interpret", 1 << 20, 128) == "interpret"


def test_sparse_mode_never_demotes_an_explicit_kernel(monkeypatch):
    """A shape no tile can serve (l too wide for the VMEM budget, or m too
    wide for an 8-row SMEM index window): an explicit "kernel" raises, and
    "auto" on a TPU host falls to "ref", which the dispatch counter shows."""
    from repro import obs

    too_wide_l = (1 << 16, 1 << 14, None)
    too_wide_m = (1 << 16, 128, 1 << 14)
    for p, ell, m in (too_wide_l, too_wide_m):
        with pytest.raises(ValueError):
            ops._sparse_mode("kernel", p, ell, m=m)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    reg = obs.MetricsRegistry()
    monkeypatch.setattr(obs.registry, "_default", reg)
    values = jnp.ones((8, too_wide_m[2]))
    indices = jnp.zeros((8, too_wide_m[2]), jnp.int32)
    ops.spmm(values, indices, jnp.ones((too_wide_m[0], 128)))
    assert reg.counter("kernels.dispatch", op="spmm", path="ref").value == 1


def test_plan_tiles_respects_budget_and_alignment():
    """plan_tiles output is a pow2 column block ≥ 256 (lane-aligned) whose
    footprint fits the budget, at representative (p, l, dtype) corners."""
    from repro.kernels import spmm as spmm_mod

    for p, ell, vd, dd in [(512, 8, jnp.float32, jnp.float32),
                           (1 << 16, 128, jnp.float32, jnp.float32),
                           (1 << 20, 64, jnp.bfloat16, jnp.bfloat16),
                           (12288, 32, jnp.float64, jnp.float64)]:
        br, pb = spmm_mod.plan_tiles(p, ell, vd, dd)
        assert pb >= 256 and (pb & (pb - 1)) == 0
        assert br >= 8
        assert spmm_mod.tile_vmem_bytes(p, ell, vd, dd, br, pb) <= _SPMM_BUDGET


def test_spmm_tiled_matches_oracle_across_column_blocks():
    """Multi-column-block parity: force small tiles so the grid walks several
    column blocks (and padded p), checking the masked densify scatters each
    index into exactly its own block on both products."""
    from repro.kernels import spmm as spmm_mod

    n, m, p, ell = 24, 6, 1500, 16   # pads to 3 × 512 column blocks
    key = jax.random.fold_in(KEY, 1500)
    values = jax.random.normal(key, (n, m))
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(
        jax.random.fold_in(key, 1), (n, p)), m)[1].astype(jnp.int32), axis=-1)
    dense = jax.random.normal(jax.random.fold_in(key, 2), (p, ell))

    t_ref = ref.ref_spmm(values, idx, dense)
    t_k = spmm_mod.spmm(values, idx, dense, block_rows=8, block_cols=512,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref), atol=1e-5)
    y_ref = ref.ref_spmm_t(values, idx, t_ref, p)
    y_k = spmm_mod.spmm_t(values, idx, t_ref, p, block_rows=8, block_cols=512,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-4)


@pytest.mark.slow
def test_spmm_tiled_matches_oracle_at_p64k():
    """The acceptance shape: p=2^16 at l=128 compiles (interpret mode) and
    matches the jnp oracles with NO ref fallback selected by the gate."""
    from repro.kernels import spmm as spmm_mod

    n, m, p, ell = 8, 4, 1 << 16, 128
    assert ops._sparse_mode("kernel", p, ell) == "kernel"
    key = jax.random.fold_in(KEY, p)
    values = jax.random.normal(key, (n, m))
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(
        jax.random.fold_in(key, 1), (n, p)), m)[1].astype(jnp.int32), axis=-1)
    dense = jax.random.normal(jax.random.fold_in(key, 2), (p, ell))

    t_ref = ref.ref_spmm(values, idx, dense)
    t_k = spmm_mod.spmm(values, idx, dense, block_rows=8, interpret=True)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref), atol=1e-5)
    y_ref = ref.ref_spmm_t(values, idx, t_ref, p)
    y_k = spmm_mod.spmm_t(values, idx, t_ref, p, block_rows=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-4)


@pytest.mark.parametrize("vd,dd,out", [
    (jnp.bfloat16, jnp.bfloat16, jnp.float32),   # bf16·bf16 accumulates in f32
    (jnp.bfloat16, jnp.float32, jnp.float32),
    (jnp.float32, jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.float32, jnp.float32),
])
def test_spmm_mixed_dtype_parity(vd, dd, out):
    """Kernel and oracle share ONE promotion rule (promoted_dtypes /
    _spmm_out_dtype): mixed-dtype operands produce the same values to
    tolerance AND the same output dtype (the old kernel silently cast dense
    to values.dtype, degrading f32 operands to bf16 compute)."""
    n, m, p, ell = 16, 4, 512, 8
    key = jax.random.fold_in(KEY, 99)
    values = jax.random.normal(key, (n, m)).astype(vd)
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(
        jax.random.fold_in(key, 1), (n, p)), m)[1].astype(jnp.int32), axis=-1)
    dense = jax.random.normal(jax.random.fold_in(key, 2), (p, ell)).astype(dd)
    from repro.kernels import spmm as spmm_mod

    tol = 1e-5 if (vd, dd) == (jnp.float32, jnp.float32) else 5e-2
    t_ref = ref.ref_spmm(values, idx, dense)
    t_k = spmm_mod.spmm(values, idx, dense, block_rows=8, interpret=True)
    assert t_k.dtype == t_ref.dtype == out
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref), atol=tol)

    t32 = t_ref.astype(dd)
    y_ref = ref.ref_spmm_t(values, idx, t32, p)
    y_k = spmm_mod.spmm_t(values, idx, t32, p, block_rows=8, interpret=True)
    assert y_k.dtype == y_ref.dtype == out
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=tol * 4)
