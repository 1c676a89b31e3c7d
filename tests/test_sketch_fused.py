"""The fused one-pass sketch kernel vs its composed oracle, and its wiring
into the real ingest path (kernels.ops dispatch + core.sketch).

The kernel is the streaming-ingest fast path (precondition → sample in one
VMEM round trip); these tests pin (a) oracle parity across the Kronecker
regimes and ragged row counts, (b) the dispatch seams — the composed
chunked-FWHT + gather fallback above the single-tile ceiling, and (c) that
``core.sketch`` produces the SAME sketch through the fused path as through
the jnp butterfly path (bit-identical indices; values to float tolerance).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.core.sampling import sample_indices
from repro.kernels import fwht, ops, ref, sketch_fused

KEY = jax.random.PRNGKey(0)


def _case(seed, n, p, m):
    key = jax.random.fold_in(KEY, seed)
    x = jax.random.normal(key, (n, p), jnp.float32)
    s = jax.random.rademacher(jax.random.fold_in(key, 1), (p,), jnp.float32)
    idx = jnp.sort(jax.lax.top_k(jax.random.uniform(
        jax.random.fold_in(key, 2), (n, p)), m)[1].astype(jnp.int32), axis=-1)
    return x, s, idx


@pytest.mark.parametrize("n,p,m", [
    (10, 128, 8),     # a == 1 (p ≤ 256: single Kronecker factor)
    (33, 256, 16),    # a == 1 boundary
    (9, 512, 32),     # a > 1 (two-factor Kronecker)
    (21, 4096, 64),   # a > 1, wide
    (19, 2048, 300),  # three output lane tiles
    (45, 1024, 51),   # m not a multiple of 8; rows not a multiple of the block
])
def test_fused_matches_composed_oracle(n, p, m):
    x, s, idx = _case(n * p, n, p, m)
    a, b = fwht.factor_p(p)
    assert (a == 1) == (p <= 256)
    y = sketch_fused.sketch_fused(x, s, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.ref_sketch_fused(x, s, idx)),
                               atol=3e-4)


@pytest.mark.parametrize("n", [1, 7, 127, 130])
def test_fused_ragged_row_counts(n):
    """Row counts that don't divide block_rows exercise the pad/slice path."""
    x, s, idx = _case(1000 + n, n, 512, 24)
    y = sketch_fused.sketch_fused(x, s, idx, interpret=True)
    assert y.shape == (n, 24)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.ref_sketch_fused(x, s, idx)),
                               atol=3e-4)


def _edge_indices(n, p, m, case):
    """(n, m) sorted distinct indices that stress the lane-tile gather."""
    if case == "lane_tile_bounds":
        edges = [0, 127, 128, 255, p - 1]
        rest = [c for c in range(1, p - 1, p // m) if c not in edges]
        row = sorted(edges + rest[:m - len(edges)])
    else:   # every kept slot of the row in one 128-lane tile
        row = list(range(3 * 128, 3 * 128 + m))
    return jnp.tile(jnp.asarray(row, jnp.int32), (n, 1))


@pytest.mark.parametrize("case,n,p,m", [
    ("lane_tile_bounds", 11, 512, 24),
    ("lane_tile_bounds", 37, 1024, 51),
    ("one_lane_tile", 11, 512, 24),
    ("one_lane_tile", 37, 1024, 51),
])
def test_fused_gather_edge_indices(case, n, p, m):
    """Indices on lane-tile boundaries (0, 127, 128, 255, p − 1) and rows
    whose kept slots all lie in one 128-lane tile."""
    x, s, _ = _case(7 * n + p, n, p, m)
    idx = _edge_indices(n, p, m, case)
    assert idx.shape == (n, m)
    assert (jnp.diff(idx, axis=1) > 0).all()
    y = sketch_fused.sketch_fused(x, s, idx, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.ref_sketch_fused(x, s, idx)),
                               atol=3e-4)


def test_fused_rejects_past_single_tile_ceiling():
    n, p, m = 2, 2 * fwht.MAX_P_SINGLE, 8
    x, s, idx = _case(7, n, p, m)
    with pytest.raises(ValueError, match="ceiling"):
        sketch_fused.sketch_fused(x, s, idx, interpret=True)


def test_ops_dispatch_modes_agree():
    x, s, idx = _case(3, 12, 512, 32)
    y_i = ops.sketch_fused(x, s, idx, mode="interpret")
    y_r = ops.sketch_fused(x, s, idx, mode="ref")
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_r), atol=3e-4)


@pytest.mark.slow
def test_ops_composed_fallback_above_ceiling():
    """p > MAX_P_FUSED: kernel modes compose chunked FWHT + gather instead of
    erroring — same values as the oracle."""
    n, p, m = 4, 2 * fwht.MAX_P_SINGLE, 16
    x, s, idx = _case(11, n, p, m)
    y = ops.sketch_fused(x, s, idx, mode="interpret")
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.ref_sketch_fused(x, s, idx)),
                               atol=2e-3)


@pytest.mark.parametrize("p,gamma", [(512, 0.1), (300, 0.2), (2048, 0.05)])
def test_core_sketch_fused_path_equals_jnp_path(p, gamma):
    """core.sketch impl="interpret" takes the fused kernel path; it must
    produce the SAME sketch as the jnp butterfly + subsample path — indices
    bit-identical (same key, same draw shape), values to float tolerance.
    p=300 exercises the non-pow2 pad inside the fused branch."""
    n = 40
    x = jax.random.normal(jax.random.fold_in(KEY, p), (n, p), jnp.float32)
    spec = sk.make_spec(p, jax.random.PRNGKey(5), gamma=gamma)
    s_fused = sk.sketch(x, spec, impl="interpret")
    s_jnp = sk.sketch(x, spec, impl="jnp")
    assert s_fused.p == s_jnp.p == spec.p_pad
    np.testing.assert_array_equal(np.asarray(s_fused.indices),
                                  np.asarray(s_jnp.indices))
    np.testing.assert_allclose(np.asarray(s_fused.values),
                               np.asarray(s_jnp.values), atol=3e-4)


def test_fused_branch_index_draw_matches_subsample():
    """The fused branch draws indices with sample_indices under the SAME
    (key, (n, p_pad)) as subsample's internal draw — the PRNG contract that
    keeps the two ingest paths interchangeable mid-stream."""
    p, m, n = 512, 51, 13
    spec = sk.make_spec(p, jax.random.PRNGKey(9), m=m)
    x = jax.random.normal(KEY, (n, p), jnp.float32)
    s_jnp = sk.sketch(x, spec, impl="jnp")
    idx = sample_indices(spec.mask_key(), n, spec.p_pad, m)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(s_jnp.indices))
