"""Streaming sketch engine (repro.stream): streaming==batch, chunked FWHT at
large p, sharded==single-device, and mini-batch streaming K-means quality."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import estimators, kmeans as km, sampling, sketch
from repro.kernels import fwht, ref
from repro.stream import StreamEngine, StreamKMeansConfig, batch_key
from tests.conftest import make_clusters

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------- streaming equals batch ---

def test_streaming_equals_batch_mean_cov():
    """Engine over B=4 batches == one-shot Thm-4/Thm-6 estimators on the
    concatenation of the SAME per-(step, shard) sketches, to 1e-5."""
    p, m, b, steps = 64, 16, 40, 4
    spec = sketch.make_spec(p, jax.random.PRNGKey(1), m=m)
    x_all = jax.random.normal(KEY, (steps * b, p))

    def source(seed, step, shard):
        return np.asarray(x_all[step * b:(step + 1) * b])

    res = StreamEngine(spec, source, track_cov=True).run(steps)

    batches = [sketch.sketch(x_all[i * b:(i + 1) * b], spec,
                             batch_key=batch_key(spec, i, 0)) for i in range(steps)]
    s_all = sampling.SparseRows(jnp.concatenate([s.values for s in batches]),
                                jnp.concatenate([s.indices for s in batches]),
                                spec.p_pad)
    np.testing.assert_allclose(res.mean, estimators.mean_estimator(s_all),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(res.cov, estimators.cov_estimator(s_all),
                               rtol=1e-4, atol=1e-5)
    assert float(res.count) == steps * b


def test_engine_consumes_pipeline_source():
    """VectorStreamSource's (seed, step, shard) batch_at contract plugs in."""
    from repro.data.pipeline import VectorStreamSource

    src = VectorStreamSource(p=64, batch=32, seed=3)
    spec = sketch.make_spec(64, jax.random.PRNGKey(4), gamma=0.25)
    res = StreamEngine(spec, src, track_cov=False).run(3)
    assert res.mean.shape == (64,)
    assert float(res.count) == 96
    assert res.cov is None


def test_scanned_run_matches_eager_loop():
    """run_scanned (one lax.scan) is bit-identical to the step-at-a-time loop."""
    p, b, steps = 64, 32, 5
    spec = sketch.make_spec(p, jax.random.PRNGKey(5), gamma=0.25)
    data = jax.random.normal(KEY, (steps, 1, b, p))

    def source(seed, step, shard):
        return np.asarray(data[step, shard])

    eng = StreamEngine(spec, source, kmeans=StreamKMeansConfig(k=3, n_init=2))
    res_loop = eng.run(steps)
    res_scan = eng.run_scanned(np.asarray(data))
    np.testing.assert_array_equal(np.asarray(res_loop.mean), np.asarray(res_scan.mean))
    np.testing.assert_array_equal(np.asarray(res_loop.cov), np.asarray(res_scan.cov))
    np.testing.assert_array_equal(np.asarray(res_loop.centers), np.asarray(res_scan.centers))


# ------------------------------------------------------- chunked FWHT -------

@pytest.mark.parametrize("p", [1 << 16, 1 << 17])
def test_chunked_fwht_matches_reference_large_p(p):
    """The three-pass Kronecker schedule == the butterfly oracle above the old
    MAX_P = 2^15 single-tile ceiling (interpret mode, CPU)."""
    n = 2
    key = jax.random.PRNGKey(p)
    x = jax.random.normal(key, (n, p), jnp.float32)
    s = jax.random.rademacher(jax.random.fold_in(key, 1), (p,), jnp.float32)
    y = fwht.hd_precondition(x, s, interpret=True)
    np.testing.assert_allclose(y, ref.ref_hd_precondition(x, s), atol=5e-4)


@pytest.mark.slow
def test_chunked_fwht_three_factor_branch():
    """p = 2^19 exercises the a > 1 outer-factor pass (a=2, b=c=512)."""
    p = 1 << 19
    assert fwht.factor_p3(p) == (2, 512, 512)
    x = jax.random.normal(KEY, (1, p), jnp.float32)
    s = jax.random.rademacher(jax.random.PRNGKey(1), (p,), jnp.float32)
    y = fwht.hd_precondition_chunked(x, s, interpret=True)
    np.testing.assert_allclose(y, ref.ref_hd_precondition(x, s), atol=1e-3)


def test_factor_p3_properties():
    for logp in range(1, 28):
        a, b, c = fwht.factor_p3(1 << logp)
        assert a * b * c == 1 << logp
        assert max(a, b, c) <= 512
    with pytest.raises(ValueError):
        fwht.factor_p3(3 << 10)
    with pytest.raises(ValueError):
        fwht.factor_p3(1 << 28)


def test_ros_kernel_impl_roundtrip_large_p():
    """precondition(impl=interpret) routes through the chunked kernel and stays
    an isometry (so all the paper's guarantees carry over at p = 2^16)."""
    from repro.core import ros

    p = 1 << 16
    x = jax.random.normal(KEY, (2, p))
    y = ros.precondition(x, KEY, "hadamard", impl="interpret")
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=1),
                               jnp.linalg.norm(x, axis=1), rtol=1e-4)
    np.testing.assert_allclose(ros.unmix(y, KEY, "hadamard"), x, atol=1e-3)


# ------------------------------------------ mini-batch streaming K-means ----

def test_streaming_kmeans_matches_batch_accuracy():
    """One-pass mini-batch streaming K-means reaches >= the clustering accuracy
    of the full-Lloyd sparse_kmeans_core on the blobs fixture."""
    x, labels, true_centers = make_clusters(KEY, n=1500, p=128, k=5)
    b = 150
    spec = sketch.make_spec(128, jax.random.PRNGKey(2), gamma=0.25)

    def source(seed, step, shard):
        return np.asarray(x[step * b:(step + 1) * b])

    eng = StreamEngine(spec, source, kmeans=StreamKMeansConfig(k=5, n_init=3))
    res = eng.run(10)
    s_all = sketch.sketch(x, spec)
    acc_stream = km.clustering_accuracy(eng.assign(s_all), labels, 5)
    mu, a_b, _, _ = km.sparse_kmeans_core(s_all.values, s_all.indices, s_all.p, 5,
                                          spec.signs_key(), n_init=3, max_iter=50)
    acc_batch = km.clustering_accuracy(a_b, labels, 5)
    assert acc_stream >= acc_batch, (acc_stream, acc_batch)
    # unmixed centers land near the true generating centers
    from scipy.optimize import linear_sum_assignment

    d = np.linalg.norm(np.asarray(res.centers)[:, None, :]
                       - np.asarray(true_centers)[None], axis=-1)
    ri, ci = linear_sum_assignment(d)
    assert float(d[ri, ci].mean()) < 2.0


def test_stream_launcher_smoke(capsys):
    """The CLI driver wires source→engine→finalize end-to-end."""
    from repro.launch import stream as launch_stream

    launch_stream.main(["--p", "256", "--gamma", "0.1", "--steps", "2",
                        "--batch", "32", "--no-cov"])
    out = capsys.readouterr().out
    assert "streamed 64 rows" in out


# ------------------------------------------------------ sharded streaming ---

@pytest.mark.slow
def test_sharded_streaming_matches_single_device():
    """8-way shard_map streaming == single-device streaming, bit-for-bit here
    (identical per-(step, shard) sketches; one psum of the deltas per step).
    Subprocess so the test session keeps the real single device."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import sketch
        from repro.stream import StreamEngine, StreamKMeansConfig

        mesh = jax.make_mesh((8,), ("data",))
        p, b, steps = 256, 16, 5
        spec = sketch.make_spec(p, jax.random.PRNGKey(1), gamma=0.25)
        data = jax.random.normal(jax.random.PRNGKey(0), (steps, 8, b, p))

        def source(seed, step, shard):
            return np.asarray(data[step, shard])

        cfg = dict(n_shards=8, kmeans=StreamKMeansConfig(k=4, n_init=2))
        res1 = StreamEngine(spec, source, **cfg).run(steps)
        res8 = StreamEngine(spec, source, mesh=mesh, **cfg).run(steps)
        np.testing.assert_allclose(np.asarray(res8.mean), np.asarray(res1.mean), atol=1e-5)
        np.testing.assert_allclose(np.asarray(res8.cov), np.asarray(res1.cov), atol=1e-5)
        np.testing.assert_allclose(np.asarray(res8.centers), np.asarray(res1.centers), atol=1e-5)
        assert float(res8.count) == steps * 8 * b

        # one-shot shard_map reductions handle row counts that don't divide the
        # mesh (zero-pad rows contribute nothing; count stays the true n)
        from repro.core import estimators
        from repro.stream import sharded as dist
        x = jax.random.normal(jax.random.PRNGKey(2), (100, p))
        s = sketch.sketch(x, spec)
        np.testing.assert_allclose(np.asarray(dist.sharded_mean(s, mesh)),
                                   np.asarray(estimators.mean_estimator(s)), atol=1e-5)
        np.testing.assert_allclose(np.asarray(dist.sharded_cov(s, mesh)),
                                   np.asarray(estimators.cov_estimator(s)), atol=1e-4)
        print("sharded-streaming OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


# -------------------------------------------------------- the step fold ---

_FOLD_SCRIPT = """
import json
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from repro import lowrank
from repro.core import sketch
from repro.core.sampling import SparseRows
from repro.stream import accumulators as acc, sharded, step

mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
p, b = 128, 24
spec = sketch.make_spec(p, jax.random.PRNGKey(1), gamma=0.25)
x = jax.random.normal(jax.random.PRNGKey(0), (8, b, p))
sk = [sketch.sketch(x[i], spec, batch_key=sketch.batch_key(spec, 3, i)) for i in range(8)]
values = jnp.stack([s.values for s in sk])
indices = jnp.stack([s.indices for s in sk])
km0 = acc.kmeans_init(jax.random.PRNGKey(2), sk[0], 4, 2)
folds = {
    "moment": (("moment", "dense"), acc.moment_init(spec.p_pad, track_cov=True), None),
    "range": (("range", "auto"), lowrank.range_init(spec.p_pad, 6),
              lowrank.omega(spec.key, spec.p_pad, 6)),
    "kmeans": (("kmeans", True, 1.0), km0, None),
}

def diff(a, b):
    # what |a - b| <= atol + rtol·|b| leaves of atol, at rtol 1e-5: the
    # objective sums many distances, so its float reordering is relative
    return max(float(jnp.max(jnp.abs(u - v) - 1e-5 * jnp.abs(v)))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b))
               for u, v in [(jnp.asarray(u, jnp.float32), jnp.asarray(v, jnp.float32))])

report = {}
for kind, (desc, carry, aux) in folds.items():
    host, host_counts = step.fold_step(desc, carry, aux, sk)

    def body(carry, v, i, desc=desc, aux=aux):
        return step.fold_step(desc, carry, aux, [SparseRows(v[0], i[0], spec.p_pad)],
                              reduce=lambda t: jax.lax.psum(t, "data"))

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                           out_specs=P(), check_vma=False))
    got, got_counts = fn(carry, values, indices)
    report[kind] = {"diff": diff(host, got),
                    "counts": [np.asarray(host_counts).tolist(), np.asarray(got_counts).tolist()],
                    "count": [int(host.count), int(got.count)]}

# 100 rows over 8 shards: 4 masked pad rows
s100 = sketch.sketch(x.reshape(-1, p)[:100], spec, batch_key=sketch.batch_key(spec, 3, 0))
host, host_counts = step.fold_step(("kmeans", True, 1.0), km0, None, [s100])
got, got_counts = sharded.sharded_kmeans_step(km0, s100, mesh, track_reassignments=True)
report["masked"] = {"diff": diff(host, got),
                    "counts": [np.asarray(host_counts).tolist(), np.asarray(got_counts).tolist()],
                    "count": [int(host.count), int(got.count)]}
print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def fold_report():
    """One 8-host-device interpreter computes every case below."""
    import json

    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _FOLD_SCRIPT], env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", ["moment", "range", "kmeans"])
def test_fold_step_psum_matches_host(fold_report, kind):
    """fold_step under shard_map with a psum over 8 devices == the host
    fold_step over the same 8 shard sketches (atol 1e-5, rtol 1e-5;
    reassignment counts exact)."""
    got = fold_report[kind]
    assert got["diff"] <= 1e-5, got
    assert got["counts"][0] == got["counts"][1]
    assert got["count"] == [8 * 24, 8 * 24]


def test_sharded_kmeans_step_masks_pad_rows(fold_report):
    """100 rows over 8 shards: the 4 pad rows move no center, count no
    reassignment and add no row, so the step == the host fold on 100 rows."""
    got = fold_report["masked"]
    assert got["diff"] <= 1e-5, got
    assert got["counts"][0] == got["counts"][1]
    assert got["count"] == [100, 100]


def test_fd_fold_refuses_reduce():
    """Frequent Directions is order-dependent: no cross-shard reduce."""
    from repro import lowrank
    from repro.stream import step

    s = sketch.sketch(jax.random.normal(KEY, (8, 64)), sketch.make_spec(64, KEY, gamma=0.25))
    carry = lowrank.fd_init(64, 4)
    folded, _ = step.fold_step(("fd",), carry, None, [s])
    assert int(folded.count) == 8
    with pytest.raises(ValueError, match="order-dependent"):
        step.fold_step(("fd",), carry, None, [s], reduce=lambda t: t)


def test_planted_kmeans_apply_reaches_every_driver(monkeypatch):
    """A K-means apply replaced on its module (as the benchmark's planted
    faults do) is the one every driver runs: the engine, the sharded step
    and the api's scan all leave their centers where they started."""
    from jax.sharding import Mesh

    from repro.api import Plan, SparsifiedKMeans, fit_many
    from repro.stream import accumulators as acc
    from repro.stream import sharded

    def kmeans_apply(state, delta, decay=1.0):
        return type(state)(state.centers, state.counts, state.obj, state.count + delta[3])

    monkeypatch.setattr(acc, "kmeans_apply", kmeans_apply)
    p, b = 64, 32
    spec = sketch.make_spec(p, jax.random.PRNGKey(6), gamma=0.25)
    data = np.asarray(jax.random.normal(KEY, (3, 2, b, p)))

    eng = StreamEngine(spec, lambda seed, step, shard: data[step, shard], n_shards=2,
                       kmeans=StreamKMeansConfig(k=3, n_init=2, track_reassignments=True))
    start = eng.init_state()
    eng.run(3)
    np.testing.assert_array_equal(eng.state.kmeans.centers, start.kmeans.centers)
    assert int(eng.state.kmeans.count) == 3 * 2 * b

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    s = sketch.sketch(data[0, 0], spec)
    new, _ = sharded.sharded_kmeans_step(start.kmeans, s, mesh, track_reassignments=True)
    np.testing.assert_array_equal(new.centers, start.kmeans.centers)
    assert int(new.count) == b

    plan = Plan(backend="stream", gamma=0.25, batch_size=b)
    rows = data.reshape(-1, p)
    one, three = (SparsifiedKMeans(3, plan, key=1, algorithm="minibatch")
                  for _ in range(2))
    fit_many(plan, [one], rows[:b], scan=True)
    fit_many(plan, [three], rows, scan=True)
    np.testing.assert_array_equal(three.centers_pre_, one.centers_pre_)
    assert float(three.objective_) == 0.0 and three.count_ == 6 * b
