"""The sketch-serving subsystem (repro.sketchserve): service lifecycle parity
with direct fits, shared-sketch groups, micro-batch coalescing, admission
control, lazy finalization, snapshot/restore bit-identity, the multi-worker
pool (per-group ordering, stop/submit races), the auto-snapshot policy,
tenant TTL/LRU eviction, the QueueSource stream adapter, and the SketchCursor
concurrent-producer contract."""
import queue
import threading
import time

import jax
import numpy as np
import pytest

from repro.api import (Plan, SparsifiedCov, SparsifiedKMeans, SparsifiedMean,
                       SparsifiedPCA, fit_many)
from repro.sketchserve import (AdminRequest, IngestRequest, QueryRequest,
                               SketchService, SnapshotPolicy, restore_service)
from repro.stream import QueueSource
from tests.conftest import make_clusters, spiked

KEY = jax.random.PRNGKey(0)
P = 32
BS = 64


def _plan(**kw):
    base = dict(backend="stream", gamma=0.5, batch_size=BS)
    base.update(kw)
    return Plan(**base)


def _x(n=256, p=P, seed=0):
    return np.asarray(spiked(jax.random.PRNGKey(seed), n, p, 3),
                      np.float32)


# ------------------------------------------------------------ fit parity ----


@pytest.mark.parametrize("kind,params,op,attr", [
    ("mean", {}, "mean", "mean_"),
    ("cov", {}, "cov", "cov_"),
    ("pca", {"n_components": 3}, "components", None),
    ("kmeans", {"k": 3}, "centers", "centers_"),
])
def test_served_tenant_matches_direct_fit(kind, params, op, attr):
    """Queue → coalesce → fold → lazy finalize ends bit-identical to the
    direct estimator fit: requests sized in batch_size multiples keep the
    chunk boundaries (hence (step, shard) mask keys) exactly fit(x)'s.
    scan='never' pins both sides to the same host fold loop."""
    x = _x(256)
    plan = _plan() if kind != "pca" else _plan(cov_path="lowrank", rank=12)
    from repro.sketchserve.service import ESTIMATORS

    direct = ESTIMATORS[kind](plan=plan, key=3, **params).fit(x)
    with SketchService(scan="never") as svc:
        svc.create_tenant("t", kind, plan=plan, key=3, **params)
        futs = [svc.ingest("t", x[i:i + 2 * BS]) for i in range(0, 256, 2 * BS)]
        assert all(f.result().ok for f in futs)
        got = svc.query("t", op).unwrap()
    if kind == "pca":
        np.testing.assert_array_equal(got["components"],
                                      np.asarray(direct.components_))
        np.testing.assert_array_equal(got["explained_variance"],
                                      np.asarray(direct.explained_variance_))
    else:
        np.testing.assert_array_equal(got, np.asarray(getattr(direct, attr)))


def test_group_shares_one_compression_pass():
    """Co-registered tenants ride ONE cursor: n_sketches counts chunks, not
    chunks × tenants, and both results equal the fit_many twins."""
    x = _x(256)
    plan = _plan(cov_path="lowrank", rank=12)
    with SketchService(scan="never") as svc:
        svc.create_tenant("p", "pca", plan=plan, key=7, n_components=3,
                          group="g")
        svc.create_tenant("k", "kmeans", plan=_plan(), key=7, k=3, group="g",
                          algorithm="minibatch")
        svc.ingest("g", x).result()
        st = svc.query("p", "stats").unwrap()
        assert st["n_sketches"] == st["chunks"] == 4      # 256 rows / bs=64
        comps = svc.query("p", "components").unwrap()["components"]
        centers = svc.query("k", "centers").unwrap()
    pca = SparsifiedPCA(3, plan, key=7)
    km = SparsifiedKMeans(3, _plan(), key=7, algorithm="minibatch")
    fit_many(plan, [pca, km], x)
    np.testing.assert_array_equal(comps, np.asarray(pca.components_))
    np.testing.assert_array_equal(centers, np.asarray(km.centers_))


def test_group_geometry_and_key_checks():
    plan = _plan()
    with SketchService() as svc:
        svc.create_tenant("a", "mean", plan=plan, key=1, group="g")
        # sketch geometry must agree across the shared pass
        with pytest.raises(RuntimeError, match="gamma"):
            svc.create_tenant("b", "mean", plan=_plan(gamma=0.25), key=1,
                              group="g")
        # shared sketch ⇒ shared randomness
        with pytest.raises(RuntimeError, match="key"):
            svc.create_tenant("c", "mean", plan=plan, key=2, group="g")
        # late joiners would silently miss folded rows — refused
        svc.ingest("g", _x(BS)).result()
        with pytest.raises(RuntimeError, match="already ingested"):
            svc.create_tenant("d", "mean", plan=plan, key=1, group="g")
        # duplicate ids, unknown kinds
        with pytest.raises(RuntimeError, match="exists"):
            svc.create_tenant("a", "mean", plan=plan, key=1)
        with pytest.raises(RuntimeError, match="kind"):
            svc.create_tenant("e", "median", plan=plan, key=1)


# ---------------------------------------------------------- micro-batching --


def _drain(svc):
    """Pull everything submit() queued and serve it through one worker sweep
    (the un-started-service idiom: deterministic micro-batch contents)."""
    items = []
    while True:
        try:
            items.append(svc._queue.get_nowait())
        except queue.Empty:
            break
    svc._process(items)


def test_contiguous_ingest_coalesces_into_one_fold():
    svc = SketchService()          # not started: we drive the drain by hand
    plan = _plan()
    svc.create_tenant("t", "mean", plan=plan, key=1)
    futs = [svc.ingest("t", _x(BS, seed=i)) for i in range(3)]
    _drain(svc)
    acks = [f.result(0) for f in futs]
    assert all(a.ok and a.info["coalesced"] == 3 for a in acks)
    assert svc.stats["ingest_folds"] == 1          # ONE sketch+fold sweep
    assert svc.stats["ingest_requests"] == 3
    # a query splits the run: ingest-query-ingest = two folds, ordered
    f1 = svc.ingest("t", _x(BS))
    q = svc.submit(QueryRequest("t", "stats"))
    f2 = svc.ingest("t", _x(BS))
    _drain(svc)
    assert f1.result(0).ok and f2.result(0).ok
    assert q.result(0).unwrap()["rows"] == 4 * BS   # saw f1, not f2
    assert svc.stats["ingest_folds"] == 3


def test_coalesced_fold_is_a_valid_estimate():
    """Coalescing moves chunk boundaries (different (step, shard) keys than
    request-at-a-time folding) — the estimate stays unbiased. Ragged
    requests (row counts off the batch grid) coalesce into one pass whose
    mean matches the data's. The rows are enough for the sketch's own
    sampling error (about 0.01 per coordinate at γ = 0.5) to sit well inside
    the tolerance whatever the key's draws."""
    rng = np.random.default_rng(1)
    mu = rng.normal(size=P).astype(np.float32)
    rows = 400
    blocks = [mu + 0.1 * rng.normal(size=(rows, P)).astype(np.float32)
              for _ in range(40)]
    svc = SketchService()
    svc.create_tenant("t", "mean", plan=_plan(gamma=0.5), key=1)
    futs = [svc.ingest("t", b) for b in blocks]
    _drain(svc)
    assert all(f.result(0).ok for f in futs)
    assert svc.stats["ingest_folds"] == 1
    with svc:
        got = svc.query("t", "mean").unwrap()
        assert svc.query("t", "stats").unwrap()["rows"] == 40 * rows
    np.testing.assert_allclose(got, np.concatenate(blocks).mean(0), atol=0.05)


def test_scan_burst_path_matches_host_loop():
    """A drained burst spanning full steps goes through the jitted lax.scan
    ingest; results match the host loop to float-summation reordering."""
    x = _x(4 * BS)
    outs = {}
    for mode in ("auto", "never"):
        with SketchService(scan=mode) as svc:
            svc.create_tenant("t", "pca", plan=_plan(cov_path="lowrank",
                                                     rank=12),
                              key=3, n_components=3)
            svc.ingest("t", x).result()
            outs[mode] = svc.query("t", "components").unwrap()["components"]
            assert svc._groups["t"].cursor.scan is False   # reset after burst
    np.testing.assert_allclose(outs["auto"], outs["never"], atol=1e-5)


# ------------------------------------------------------- admission control --


def test_admission_rejects_with_backpressure():
    svc = SketchService(max_pending_rows=2 * BS, max_queue=3)
    svc.create_tenant("t", "mean", plan=_plan(), key=1)
    a = svc.ingest("t", _x(2 * BS))                 # admitted: hits the cap
    b = svc.ingest("t", _x(BS))                     # over the row cap
    assert b.result(0).status == "rejected" and "pending" in b.result(0).error
    c = svc.ingest("unknown", _x(1))                # unknown target: error
    assert c.result(0).status == "error"
    _drain(svc)
    assert a.result(0).ok
    d = svc.ingest("t", _x(BS))                     # backlog folded: admitted
    assert not d.done()
    # queue-depth cap: fill the (tiny) queue, next submit bounces
    e = [svc.ingest("t", _x(1)) for _ in range(3)]
    assert e[-1].result(0).status == "rejected"
    assert "queue full" in e[-1].result(0).error
    assert svc.stats["rejected"] >= 2


def test_mismatched_width_coalesced_run_answers_errors_and_survives():
    """Two same-group ingests with different column counts coalesce into one
    run whose concatenate fails: every request in the run gets an error
    response, the pending-row reservation is released, and the fold path
    keeps serving — the failure must never escape and kill the worker."""
    svc = SketchService()
    svc.create_tenant("t", "mean", plan=_plan(), key=1)
    a = svc.ingest("t", _x(BS))
    bad = svc.ingest("t", np.zeros((4, P + 1), np.float32))
    _drain(svc)
    assert a.result(0).status == "error"
    assert "ingest failed" in bad.result(0).error
    assert svc._groups["t"].pending_rows == 0
    ok = svc.ingest("t", _x(BS))               # the next fold succeeds
    _drain(svc)
    assert ok.result(0).ok
    # once the group's width is pinned by a fold, mismatches bounce at submit
    # (per-request error, no longer able to poison a coalesced run)
    bad2 = svc.ingest("t", np.zeros((4, P + 1), np.float32))
    assert bad2.done() and "columns" in bad2.result(0).error
    assert svc._groups["t"].pending_rows == 0


def test_worker_survives_internal_errors(monkeypatch):
    """An exception escaping a _process sweep fails that batch's futures with
    an error response instead of silently killing the single worker thread."""
    with SketchService() as svc:
        svc.create_tenant("t", "mean", plan=_plan(), key=1)

        def boom(req):
            raise RuntimeError("boom")

        monkeypatch.setattr(svc, "_handle_query", boom)
        r = svc.query("t", "stats", timeout=5)
        assert r.status == "error" and "boom" in r.error
        monkeypatch.undo()
        assert svc._thread.is_alive()          # worker lived through it
        assert svc.ingest("t", _x(BS)).result(5).ok
        assert svc.query("t", "mean", timeout=5).ok


def test_stop_fails_late_submissions_instead_of_hanging():
    """After stop(), every request family resolves immediately with an error
    response — nothing enqueues into the dead queue and hangs forever — and
    rejected ingest never leaks a pending-row reservation."""
    svc = SketchService()
    svc.create_tenant("t", "mean", plan=_plan(), key=1)
    with svc:
        assert svc.ingest("t", _x(BS)).result(5).ok
    for f in (svc.ingest("t", _x(BS)),
              svc.submit(QueryRequest("t", "stats")),
              svc.submit(AdminRequest("delete_tenant", dict(tid="t")))):
        assert f.done() and "stopped" in f.result(0).error
    assert svc._groups["t"].pending_rows == 0
    with pytest.raises(RuntimeError, match="stopped"):
        svc.start()                            # no restart onto dead state


def test_submit_never_mutates_caller_request():
    """A retained IngestRequest keeps its original target and rows payload —
    coercion and group-id normalization happen on the internal queue record."""
    svc = SketchService()
    svc.create_tenant("t", "mean", plan=_plan(), key=1, group="g")
    rows = [[1.0] * P]
    req = IngestRequest("t", rows)
    fut = svc.submit(req)
    assert req.target == "t" and req.rows is rows
    _drain(svc)
    assert fut.result(0).ok and fut.result(0).info["group"] == "g"


def test_lazy_finalization_only_on_stale_reads():
    with SketchService() as svc:
        svc.create_tenant("t", "pca", plan=_plan(cov_path="lowrank", rank=12),
                          key=3, n_components=3)
        # reads before any ingest are an error, not a crash
        assert "no ingested rows" in svc.query("t", "components").error
        svc.ingest("t", _x(2 * BS)).result()
        svc.query("t", "components").unwrap()
        svc.query("t", "transform", _x(8)).unwrap()
        assert svc.query("t", "stats").unwrap()["finalize_count"] == 1  # reused
        svc.ingest("t", _x(2 * BS)).result()
        svc.query("t", "components").unwrap()       # state moved: refinalize
        assert svc.query("t", "stats").unwrap()["finalize_count"] == 2
        # op/kind mismatch answers an error response
        assert svc.query("t", "centers").status == "error"
        assert svc.query("t", "nope").status == "error"


# ------------------------------------------------------- snapshot/restore ---


def test_snapshot_restore_bit_identical_and_resumable(tmp_path):
    x, more = _x(4 * BS), _x(2 * BS, seed=9)
    plan = _plan(cov_path="lowrank", rank=12)
    with SketchService() as svc:
        svc.create_tenant("p", "pca", plan=plan, key=7, n_components=3,
                          group="g", retain_ingest=True)
        svc.create_tenant("k", "kmeans", plan=_plan(), key=7, k=3, group="g",
                          algorithm="minibatch")
        svc.create_tenant("solo", "cov", plan=_plan(gamma=0.25), key=5)
        svc.ingest("g", x).result()
        svc.ingest("solo", x).result()
        comps = svc.query("p", "components").unwrap()
        assert svc.snapshot(str(tmp_path)) == 1
        svc.ingest("g", more).result()
        cont = svc.query("p", "components").unwrap()

    svc2 = restore_service(str(tmp_path))
    with svc2:
        # identical reads...
        comps2 = svc2.query("p", "components").unwrap()
        np.testing.assert_array_equal(comps["components"], comps2["components"])
        st = svc2.query("solo", "stats").unwrap()
        assert st["rows"] == 4 * BS and st["chunks"] == 4
        # ...and identical continuation: same rows → same (step, shard) keys
        svc2.ingest("g", more).result()
        cont2 = svc2.query("p", "components").unwrap()
        np.testing.assert_array_equal(cont["components"], cont2["components"])
        # the retained ingest buffer survives too (refine replay after restore)
        r = svc2.refine("p", passes=1)
        assert r.ok and r.result["passes"] == 1
    # an empty (never-ingested) tenant snapshots and restores as empty
    with SketchService() as s3:
        s3.create_tenant("fresh", "mean", plan=_plan(), key=0)
        s3.snapshot(str(tmp_path / "empty"))
    with restore_service(str(tmp_path / "empty")) as s4:
        assert "no ingested rows" in s4.query("fresh", "mean").error


def test_snapshot_mesh_plan_roundtrip(tmp_path):
    """A Plan holding an explicit mesh snapshots as its GEOMETRY (axis names
    + shape, repro.api.plan.mesh_spec) and restores as an equivalent mesh on
    the restoring host's devices — bit-identical queries either side."""
    mesh = jax.make_mesh((1,), ("data",))
    x = _x(2 * BS)
    with SketchService() as svc:
        svc.create_tenant("t", "mean", plan=_plan(backend="sharded", mesh=mesh),
                          key=1)
        svc.ingest("t", x).result()
        ref = svc.query("t", "mean").unwrap()
        svc.snapshot(str(tmp_path))
    with restore_service(str(tmp_path)) as s2:
        got = s2.query("t", "mean").unwrap()
        np.testing.assert_array_equal(ref, got)
        restored = s2._groups["t"].plan.mesh
        assert restored is not None
        assert restored.axis_names == ("data",) and restored.shape["data"] == 1


# ------------------------------------------------------------ QueueSource ---


def test_queue_source_feeds_fit_stream():
    """QueueSource bridges pushed chunks to the (seed, step, shard) contract:
    fit_stream over the queue == fit over the concatenation."""
    x = _x(4 * BS)
    qs = QueueSource()
    for i in range(0, 4 * BS, BS):
        qs.push(x[i:i + BS])
    qs.close()
    plan = _plan()
    est = SparsifiedMean(plan, key=3).fit_stream(qs, steps=qs.steps())
    ref = SparsifiedMean(plan, key=3).fit(x)
    np.testing.assert_array_equal(np.asarray(est.mean_), np.asarray(ref.mean_))
    # retained chunks replay (a second pass re-reads the buffer)
    est2 = SparsifiedMean(plan, key=3).fit_stream(qs, steps=qs.steps())
    np.testing.assert_array_equal(np.asarray(est2.mean_), np.asarray(est.mean_))


def test_queue_source_contract_errors():
    qs = QueueSource(retain=False, timeout=0.05)
    qs.push(np.zeros((4, P), np.float32))
    qs.batch_at(0, 0)
    with pytest.raises(RuntimeError, match="dropped"):
        qs.batch_at(0, 0)                      # retain=False: served once
    with pytest.raises(TimeoutError, match="stalled"):
        qs.batch_at(1, 0)                      # producer never caught up
    qs.close()
    with pytest.raises(RuntimeError, match="closed"):
        qs.batch_at(1, 0)                      # past the end fails fast now
    with pytest.raises(RuntimeError, match="close"):
        qs.push(np.zeros((4, P), np.float32))
    with pytest.raises(ValueError, match="shape"):
        QueueSource().push(np.zeros(4, np.float32))


# -------------------------------------------------------- multi-worker pool --


def test_multiworker_per_group_results_bit_identical():
    """The disjoint group partition keeps one producer per cursor: the same
    request sequence through 4 workers ends bit-identical PER GROUP to the
    single-worker service (batch_size-multiple blocks + scan='never' pin the
    chunk boundaries and the host fold loop)."""
    n_groups, plan = 6, _plan(cov_path="lowrank", rank=12)
    blocks = [(f"g{r % n_groups}", _x(BS, seed=r)) for r in range(18)]

    def run(workers):
        with SketchService(workers=workers, scan="never") as svc:
            for g in range(n_groups):
                svc.create_tenant(f"t{g}", "pca", plan=plan, key=7,
                                  n_components=3, group=f"g{g}")
            futs = [svc.ingest(gid, b) for gid, b in blocks]
            assert all(f.result(60).ok for f in futs)
            return {g: svc.query(f"t{g}", "components").unwrap()["components"]
                    for g in range(n_groups)}

    one, four = run(1), run(4)
    for g in range(n_groups):
        np.testing.assert_array_equal(one[g], four[g])


def test_multiworker_routing_is_disjoint_and_stable():
    svc = SketchService(workers=4)
    owners = {g: svc._worker_of(g) for g in (f"g{i}" for i in range(64))}
    assert set(owners.values()) == set(range(4))   # every worker owns groups
    svc2 = SketchService(workers=4)
    assert owners == {g: svc2._worker_of(g) for g in owners}  # restart-stable


def test_multiworker_stop_races_inflight_ingest():
    """stop() racing a storm of in-flight ingest across ≥2 workers: every
    Future resolves (ok, rejected, or 'service stopped' — never dangles), the
    pending-row accounting lands at exactly 0, and the pending gauge agrees
    (the _fail_queued release path, per queue)."""
    n_groups = 8
    svc = SketchService(workers=4, max_queue=16)
    for g in range(n_groups):
        svc.create_tenant(f"t{g}", "mean", plan=_plan(), key=1, group=f"g{g}")
    futs: list = []
    start = threading.Barrier(3)

    def producer(seed):
        rng = np.random.default_rng(seed)
        start.wait()
        for r in range(120):
            g = int(rng.integers(n_groups))
            futs.append(svc.ingest(f"g{g}", _x(BS, seed=r)))

    svc.start()
    threads = [threading.Thread(target=producer, args=(s,)) for s in (1, 2)]
    for t in threads:
        t.start()
    start.wait()                     # both producers firing
    svc.stop()                       # races the in-flight storm
    for t in threads:
        t.join()
    for f in futs:
        assert f.done(), "a Future was left unresolved by stop()"
        assert f.result(0).status in ("ok", "rejected", "error")
    for g in range(n_groups):
        grp = svc._groups.get(f"g{g}")
        assert grp is None or grp.pending_rows == 0
    assert svc.registry.gauge("serve.pending_rows").value == 0
    assert svc.registry.gauge("serve.queue_depth").value == 0


def test_rejected_requests_are_latency_accounted():
    """Satellite: the submit-side rejected/stopped fast paths must route
    through _resolve_fut — rejections (and unknown-target errors) appear in
    serve.request_seconds alongside accepted requests."""
    svc = SketchService(max_pending_rows=BS)
    svc.create_tenant("t", "mean", plan=_plan(), key=1)
    h = svc.registry.histogram("serve.request_seconds")
    base = h.count
    svc.ingest("t", _x(BS))                        # admitted (queued)
    assert svc.ingest("t", _x(BS)).result(0).status == "rejected"
    assert svc.ingest("nope", _x(1)).result(0).status == "error"
    assert h.count == base + 2, (
        "rejected + error fast paths missing from the histogram")
    _drain(svc)                                    # resolves the admitted one
    assert h.count == base + 3


# ------------------------------------------------------ snapshot supervision --


def test_snapshot_policy_validation():
    with pytest.raises(ValueError, match="every_rows"):
        SnapshotPolicy()
    with pytest.raises(ValueError, match="every_rows"):
        SnapshotPolicy(every_rows=0)
    with pytest.raises(ValueError, match="snapshot_dir"):
        SketchService(snapshot_policy=SnapshotPolicy(every_rows=1))


def test_auto_snapshot_every_rows(tmp_path):
    d = str(tmp_path / "auto")
    with SketchService(scan="never",
                       snapshot_policy=SnapshotPolicy(every_rows=2 * BS),
                       snapshot_dir=d) as svc:
        svc.create_tenant("t", "mean", plan=_plan(), key=1)
        for i in range(4):
            svc.ingest("t", _x(BS, seed=i)).result(30).unwrap()
        deadline = time.monotonic() + 30
        while svc.stats["snapshots"] < 2:
            assert time.monotonic() < deadline, "every_rows policy never fired"
            time.sleep(0.02)
        # idle: no new rows folded → no further snapshots rewrite the dir
        n = svc.stats["snapshots"]
        time.sleep(0.35)
        assert svc.stats["snapshots"] == n
    with restore_service(d) as svc2:
        assert svc2.query("t", "stats").unwrap()["rows"] % BS == 0


def test_auto_snapshot_every_s(tmp_path):
    d = str(tmp_path / "auto")
    with SketchService(scan="never",
                       snapshot_policy=SnapshotPolicy(every_s=0.05),
                       snapshot_dir=d) as svc:
        svc.create_tenant("t", "mean", plan=_plan(), key=1)
        svc.ingest("t", _x(BS)).result(30).unwrap()
        deadline = time.monotonic() + 30
        while svc.stats["snapshots"] < 1:
            assert time.monotonic() < deadline, "every_s policy never fired"
            time.sleep(0.02)
        n = svc.stats["snapshots"]
        time.sleep(0.3)                 # idle — the timer alone must NOT fire
        assert svc.stats["snapshots"] == n
        svc.ingest("t", _x(BS, seed=1)).result(30).unwrap()
        deadline = time.monotonic() + 30
        while svc.stats["snapshots"] < n + 1:   # new rows → fires again
            assert time.monotonic() < deadline
            time.sleep(0.02)


def test_restored_snapshot_step_continues(tmp_path):
    """Satellite: snapshot → restore → snapshot lands at step N+1 — a
    restored service must never clobber the original run's earlier
    checkpoints under the same path."""
    d = str(tmp_path / "snap")
    with SketchService() as svc:
        svc.create_tenant("t", "mean", plan=_plan(), key=1)
        svc.ingest("t", _x(BS)).result()
        assert svc.snapshot(d) == 1
        assert svc.snapshot(d) == 2
    with restore_service(d) as svc2:
        assert svc2.snapshot(d) == 3
    with restore_service(d) as svc3:
        assert svc3.snapshot(d) == 4


def test_multiworker_snapshot_quiesces_at_fold_boundary(tmp_path):
    """A snapshot on a live 4-worker service quiesces the pool: the written
    state restores cleanly and the service keeps serving afterwards."""
    d = str(tmp_path / "snap")
    with SketchService(workers=4, scan="never") as svc:
        for g in range(8):
            svc.create_tenant(f"t{g}", "mean", plan=_plan(), key=1,
                              group=f"g{g}")
        futs = [svc.ingest(f"g{r % 8}", _x(BS, seed=r)) for r in range(24)]
        step = svc.snapshot(d)         # races the in-flight folds
        assert step == 1
        assert all(f.result(60).ok for f in futs)
        assert svc.ingest("g0", _x(BS)).result(30).ok   # still serving
    with restore_service(d) as svc2:
        rows = svc2.query("t0", "stats").unwrap()["rows"]
        assert rows % BS == 0          # a fold boundary, never mid-fold


# ---------------------------------------------------------- tenant eviction --


def test_ttl_eviction_and_lazy_restore(tmp_path):
    """An idle group past ttl_s is evicted to snapshot and lazily restored
    bit-identically on the next query; an ACTIVE group is left alone."""
    with SketchService(scan="never", ttl_s=0.25,
                       evict_dir=str(tmp_path)) as svc:
        svc.create_tenant("idle", "pca", plan=_plan(cov_path="lowrank",
                                                    rank=12),
                          key=3, n_components=3)
        svc.create_tenant("hot", "mean", plan=_plan(), key=1)
        svc.ingest("idle", _x(2 * BS)).result(30).unwrap()
        ref = svc.query("idle", "components").unwrap()["components"]
        deadline = time.monotonic() + 30
        while "idle" not in svc.evicted():
            assert time.monotonic() < deadline, "TTL eviction never fired"
            svc.ingest("hot", _x(BS)).result(30)       # keeps "hot" live
            time.sleep(0.03)
        assert "idle" not in svc.tenants() and "hot" in svc.tenants()
        assert svc.stats["evictions"] >= 1
        # first touch lazily restores, bit-identical
        got = svc.query("idle", "components").unwrap()["components"]
        np.testing.assert_array_equal(ref, got)
        assert "idle" in svc.tenants() and not svc.evicted()
        assert svc.stats["evict_restores"] == 1
        # and the restored cursor continues folding
        assert svc.ingest("idle", _x(BS, seed=5)).result(30).ok


def test_max_tenants_evicts_lru_group(tmp_path):
    with SketchService(scan="never", max_tenants=2,
                       evict_dir=str(tmp_path)) as svc:
        for i in range(3):
            svc.create_tenant(f"t{i}", "mean", plan=_plan(), key=1)
            svc.ingest(f"t{i}", _x(BS, seed=i)).result(30).unwrap()
        deadline = time.monotonic() + 30
        while len(svc.tenants()) > 2:
            assert time.monotonic() < deadline, "max_tenants never enforced"
            time.sleep(0.03)
        # t0 was touched least recently → it is the evicted one
        assert svc.evicted() == ["t0"]
        # evicted state still answers (lazy restore) and matches the fold
        m = svc.query("t0", "mean").unwrap()
        ref = SparsifiedMean(_plan(), key=1).fit(_x(BS, seed=0))
        np.testing.assert_array_equal(m, np.asarray(ref.mean_))


def test_eviction_skips_groups_with_pending_ingest(tmp_path):
    """A group with admitted-but-unfolded rows is never evicted (the queued
    request would resolve against a missing group)."""
    svc = SketchService(ttl_s=0.01, evict_dir=str(tmp_path))   # not started
    svc.create_tenant("t", "mean", plan=_plan(), key=1)
    fut = svc.ingest("t", _x(BS))              # reservation held, never folds
    time.sleep(0.05)
    svc._maybe_evict(0)                        # the sweep the worker would run
    assert svc.evicted() == [] and "t" in svc.tenants()
    _drain(svc)
    assert fut.result(0).ok


# ------------------------------------- concurrent producers (the contract) --


def test_concurrent_partial_fit_serializes_correctly():
    """The SketchCursor thread-safety contract: N producer threads hammering
    one SharedSketchRun serialize whole-call — no lost chunks, exact counts,
    and the mean is a valid estimate no matter the interleaving (enough
    chunks that the sketch's sampling error stays well inside the
    tolerance whichever keys the interleaving hands each chunk)."""
    rng = np.random.default_rng(2)
    mu = rng.normal(size=P).astype(np.float32)
    n_threads, per_thread = 4, 40
    blocks = [[mu + 0.1 * rng.normal(size=(BS, P)).astype(np.float32)
               for _ in range(per_thread)] for _ in range(n_threads)]
    plan = _plan(gamma=0.5)
    run = fit_many(plan, [SparsifiedMean(plan, key=1),
                          SparsifiedCov(plan, key=1)],
                   np.zeros((0, P), np.float32), finalize=False)
    start = threading.Barrier(n_threads)
    errs = []

    def producer(i):
        try:
            start.wait()
            for b in blocks[i]:
                run.partial_fit(b)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    total = n_threads * per_thread
    assert run.count == total * BS
    assert run.n_sketches == total                 # every chunk folded once
    assert run.cursor.chunk_rows == [BS] * total
    run.finalize()
    assert all(c.count_ == total * BS for c in run)
    np.testing.assert_allclose(np.asarray(run[0].mean_), mu, atol=0.05)
