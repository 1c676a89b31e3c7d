"""repro.obs telemetry: registry thread-safety, span aggregation, JSONL
round-trip, exposition/endpoint, and the observe-only contracts — engine runs
bit-identically with telemetry on, and SketchService counters reconcile
exactly with known request totals."""
import io
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import sketch
from repro.stream import EngineTelemetry, StreamEngine, StreamKMeansConfig

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- registry -----


def test_counter_histogram_concurrent_exact_totals():
    """8 threads hammer one counter + one histogram; totals are EXACT."""
    reg = obs.MetricsRegistry()
    c = reg.counter("hammer.count")
    h = reg.histogram("hammer.obs", window=64)
    n_threads, n_iter = 8, 2000

    def work(tid):
        for i in range(n_iter):
            c.inc()
            h.observe(float(tid))

    threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    # sum of tid over all observations: n_iter * (0+1+...+7)
    assert h.sum == n_iter * sum(range(n_threads))


def test_label_sets_are_independent_series():
    reg = obs.MetricsRegistry()
    reg.counter("c", group="a").inc(2)
    reg.counter("c", group="b").inc(5)
    assert reg.counter("c", group="a").value == 2
    assert reg.counter("c", group="b").value == 5
    # same name+labels → the same object (cached identity)
    assert reg.counter("c", group="a") is reg.counter("c", group="a")


def test_histogram_summary_quantiles_and_window():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", window=8)
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["sum"] == sum(range(100))
    assert s["min"] == 0.0 and s["max"] == 99.0
    # reservoir kept the last 8 observations (92..99)
    assert 92.0 <= s["p50"] <= 99.0


def test_disabled_registry_is_shared_noop():
    reg = obs.MetricsRegistry(enabled=False)
    c, g, h = reg.counter("a"), reg.gauge("b"), reg.histogram("c")
    assert c is g is h              # ONE shared null object — zero retention
    c.inc(); g.set(4.0); h.observe(1.0)
    assert c.value == 0 and reg.metrics() == [] and reg.snapshot() == {}


def test_quantiles_helper():
    p50, p99 = obs.quantiles([1.0, 2.0, 3.0, 4.0], (0.5, 0.99))
    assert p50 == pytest.approx(2.5)
    assert all(np.isnan(v) for v in obs.quantiles([], (0.5, 0.9)))


# ---------------------------------------------------------------- spans -----


def test_span_nesting_and_totals():
    reg = obs.MetricsRegistry()
    with obs.span("outer", reg):
        assert obs.current_path() == "outer"
        with obs.span("inner", reg):
            assert obs.current_path() == "outer.inner"
        with obs.span("inner", reg):
            pass
    totals = obs.span_totals(reg)
    assert totals["outer"]["count"] == 1
    assert totals["outer.inner"]["count"] == 2
    assert totals["outer"]["total_s"] >= totals["outer.inner"]["total_s"]


class _Recorder:
    """Stands in for jax.profiler.TraceAnnotation: records (path, attrs)."""

    def __init__(self, path, **attrs):
        self.path, self.attrs = path, attrs
        _Recorder.seen.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


def test_span_annotates_full_path_and_attrs(monkeypatch):
    from repro.obs import tracing

    monkeypatch.setattr(tracing, "_Annotation", _Recorder)
    monkeypatch.setattr(tracing, "_profiling", lambda: True)
    _Recorder.seen = []
    with obs.span("ingest.partial_fit", call=4, rows=64) as top:
        with obs.span("chunk", chunk=0, rows=32) as mid:
            with obs.span("fold.kmeans", consumer=1):
                pass
    with obs.span("other"):
        pass
    assert (top, mid) == ("ingest.partial_fit", "ingest.partial_fit.chunk")
    got = [(r.path, r.attrs) for r in _Recorder.seen]
    assert got == [
        ("ingest.partial_fit", {"call": 4, "rows": 64}),
        ("ingest.partial_fit.chunk", {"chunk": 0, "rows": 32, "call": 4}),
        ("ingest.partial_fit.chunk.fold.kmeans", {"consumer": 1, "call": 4}),
        ("other", {})]
    assert obs.current_path() is None


def test_span_without_registry_writes_nothing():
    reg = obs.MetricsRegistry()
    prev = obs.set_default_registry(reg)
    try:
        with obs.span("outer"):
            with obs.span("inner", rows=3):
                pass
    finally:
        obs.set_default_registry(prev)
    assert reg.metrics() == [] and obs.span_totals(reg) == {}


def test_span_records_into_callers_registry():
    mine, default = obs.MetricsRegistry(), obs.MetricsRegistry()
    prev = obs.set_default_registry(default)
    try:
        with obs.span("engine.update", mine, step=3):
            with obs.span("inner"):             # no registry: not recorded
                pass
    finally:
        obs.set_default_registry(prev)
    totals = obs.span_totals(mine)
    assert list(totals) == ["engine.update"] and totals["engine.update"]["count"] == 1
    assert totals["engine.update"]["total_s"] > 0
    assert default.metrics() == []


def test_span_leaves_the_stack_on_error():
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    assert obs.current_path() is None


# ---------------------------------------------------------------- JSONL -----


def test_steplogger_jsonl_roundtrip_and_downsampling():
    buf = io.StringIO()
    log = obs.StepLogger(stream=buf, every=3, static={"run": "t"})
    logged = [log.log(step=s, loss=float(s)) for s in range(10)]
    assert logged == [s % 3 == 0 for s in range(10)]
    log.log(step=98, force=True, note="final")
    recs = obs.read_jsonl(io.StringIO(buf.getvalue()))
    assert [r["step"] for r in recs] == [0, 3, 6, 9, 98]
    assert all(r["run"] == "t" and "t" in r for r in recs)
    assert recs[-1]["note"] == "final"


def test_steplogger_coerces_numpy(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    log = obs.StepLogger(path=path)
    log.log(step=np.int64(0), v=np.float32(1.5), arr=np.arange(3))
    (rec,) = obs.read_jsonl(path)
    assert rec["step"] == 0 and rec["v"] == 1.5 and rec["arr"] == [0, 1, 2]
    json.dumps(rec)   # everything JSON-native after the round trip


# ------------------------------------------------- exposition + endpoint ----


def test_render_exposition_snapshot():
    reg = obs.MetricsRegistry()
    reg.counter("serve.requests", tenant="t0").inc(3)
    reg.gauge("queue.depth").set(2)
    h = reg.histogram("lat.s")
    for v in (0.5, 1.0, 1.5, 2.0):
        h.observe(v)
    text = obs.render_exposition(reg)
    assert '# TYPE serve_requests counter' in text
    assert 'serve_requests{tenant="t0"} 3' in text
    assert "queue_depth 2" in text
    assert "# TYPE lat_s summary" in text
    assert 'lat_s{quantile="0.5"}' in text
    assert "lat_s_count 4" in text and "lat_s_sum 5" in text
    assert obs.render_exposition(reg) == text   # deterministic


def test_render_exposition_survives_inf_and_nan():
    """Regression: ±Inf gauges/histogram sums used to raise OverflowError in
    the sample formatter (int(inf)), killing the whole /metrics scrape. The
    Prometheus text format spells them +Inf / -Inf (and NaN stays NaN)."""
    reg = obs.MetricsRegistry()
    reg.gauge("ratio.up").set(float("inf"))
    reg.gauge("ratio.down").set(float("-inf"))
    reg.gauge("ratio.nan").set(float("nan"))
    h = reg.histogram("weird.s")
    h.observe(float("inf"))           # poisons the sum, not the scrape
    h.observe(1.0)
    text = obs.render_exposition(reg)
    assert "ratio_up +Inf" in text
    assert "ratio_down -Inf" in text
    assert "ratio_nan NaN" in text
    assert "weird_s_sum +Inf" in text and "weird_s_count 2" in text


def test_metrics_server_endpoint():
    reg = obs.MetricsRegistry()
    reg.counter("up").inc()
    with obs.serve_metrics(reg) as srv:
        text = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert "up 1" in text
        js = json.loads(urllib.request.urlopen(
            srv.url + ".json", timeout=10).read().decode())
        assert js["up"]["value"] == 1


# ----------------------------------------------- engine: observe-only -------


def test_engine_telemetry_is_bit_identical():
    """Telemetry on vs off: EVERY finalized output is bit-identical, and the
    registry/JSONL agree with the known step/row totals."""
    p, b, steps = 64, 32, 5
    spec = sketch.make_spec(p, jax.random.PRNGKey(1), gamma=0.25)
    data = np.asarray(jax.random.normal(KEY, (steps, b, p)))

    def source(seed, step, shard):
        return data[step]

    def make_engine():
        return StreamEngine(spec, source, track_cov=True,
                            kmeans=StreamKMeansConfig(k=3, n_init=2,
                                                      track_reassignments=True))

    res_plain = make_engine().run(steps)

    reg = obs.MetricsRegistry()
    buf = io.StringIO()
    tel = EngineTelemetry(registry=reg,
                          step_logger=obs.StepLogger(stream=buf), log_every=2)
    res_tel = make_engine().run(steps, telemetry=tel)

    for field in ("mean", "cov", "centers"):
        a, bb = getattr(res_plain, field), getattr(res_tel, field)
        assert np.array_equal(np.asarray(a), np.asarray(bb)), field
    assert np.array_equal(res_plain.reassign_counts, res_tel.reassign_counts)

    assert reg.counter("engine.steps").value == steps
    assert reg.counter("engine.rows").value == steps * b
    assert reg.histogram("engine.step_seconds").count == steps
    assert reg.gauge("engine.state_bytes").value > 0
    totals = obs.span_totals(reg)
    assert totals["engine.update"]["count"] == steps
    recs = obs.read_jsonl(io.StringIO(buf.getvalue()))
    assert [r["step"] for r in recs] == [0, 2, 4]
    assert recs[-1]["rows_total"] == steps * b
    assert all("reassign_frac" in r for r in recs)


def test_engine_telemetry_on_step_callback():
    spec = sketch.make_spec(32, jax.random.PRNGKey(2), gamma=0.25)
    data = np.asarray(jax.random.normal(KEY, (3, 16, 32)))
    seen = []
    tel = EngineTelemetry(registry=obs.MetricsRegistry(),
                          on_step=seen.append)
    StreamEngine(spec, lambda s, t, sh: data[t], track_cov=False).run(
        3, telemetry=tel)
    assert [r["step"] for r in seen] == [0, 1, 2]
    assert all(r["rows"] == 16 for r in seen)


# --------------------------------------------- serving: exact reconcile -----


def test_sketchserve_metrics_reconcile_exactly():
    from repro.api import Plan
    from repro.sketchserve import SketchService

    rng = np.random.default_rng(0)
    plan = Plan(backend="stream", gamma=0.25, batch_size=64,
                cov_path="lowrank", rank=4)
    n_req, rows_per = 24, 8
    with SketchService(max_batch=16) as svc:
        svc.create_tenant("t0", "pca", plan=plan, key=1, n_components=2,
                          group="g")
        svc.create_tenant("t1", "mean", plan=plan, key=1, group="g")
        futs = [svc.ingest("g", rng.normal(size=(rows_per, 64))
                           .astype(np.float32)) for _ in range(n_req)]
        assert all(f.result(60).ok for f in futs)
        svc.query("t0", "components").unwrap()
        stats = svc.stats
        reg = svc.registry

        assert stats["ingest_requests"] == n_req
        assert stats["ingest_rows"] == n_req * rows_per
        assert stats["queries"] == 1
        # total served: 24 ingests + 1 query + 2 admin (create_tenant)
        assert stats["requests"] == n_req + 3
        # coalescing: every ingest request is accounted to exactly one fold
        h = reg.histogram("serve.coalesced_requests")
        assert h.sum == n_req and h.count == stats["ingest_folds"]
        # per-tenant fold counts: both group members advance together
        assert (reg.counter("serve.tenant_folds", tenant="t0").value
                == reg.counter("serve.tenant_folds", tenant="t1").value
                == stats["ingest_folds"])
        # everything admitted was folded: the pending gauge is back to zero
        assert reg.gauge("serve.pending_rows").value == 0
        # every request's submit→resolve latency was observed
        assert reg.histogram("serve.request_seconds").count >= n_req + 1
        # the legacy dict view is one consistent snapshot (a mapping)
        assert set(SketchService.STAT_KEYS) <= set(stats)


def test_sketchserve_rejection_counted():
    from repro.api import Plan
    from repro.sketchserve import SketchService

    plan = Plan(backend="stream", gamma=0.25, batch_size=64,
                cov_path="lowrank", rank=4)
    svc = SketchService(max_pending_rows=4)   # not started: queue never drains
    svc.create_tenant("t", "mean", plan=plan, key=1)
    first = svc.ingest("t", np.zeros((3, 64), np.float32))
    assert first.done() is False                        # admitted, pending
    resp = svc.ingest("t", np.zeros((3, 64), np.float32)).result(5)
    assert resp.status == "rejected"
    assert svc.stats["rejected"] == 1
    assert svc.registry.gauge("serve.pending_rows").value == 3
    svc.stop()


# ------------------------------------------------------- cluster heartbeat --


def test_heartbeat_merge_wire_publish():
    from repro import cluster
    from repro.stream import state as state_mod

    a = cluster.beat(5, rows=100, t=1000.0)
    b = cluster.beat(7, rows=50, t=1002.5)
    m = state_mod.merge(a, b)
    assert int(m.hosts) == 2 and int(m.step) == 7 and int(m.rows) == 150

    rt = state_mod.from_arrays(state_mod.to_arrays(m), kinds=("hb",))
    assert int(rt.hosts) == 2 and float(rt.t_first) == 1000.0

    reg = obs.MetricsRegistry()
    vals = cluster.publish(cluster.gather(m), registry=reg, now=1010.0)
    assert vals["cluster.hosts"] == 2.0
    assert vals["cluster.heartbeat_age_s"] == pytest.approx(7.5)
    assert vals["cluster.straggler_lag_s"] == pytest.approx(2.5)
    cluster.publish_local(a, host=3, registry=reg)
    assert reg.gauge("cluster.host_step", host="3").value == 5.0


# ------------------------------------------------------ kernel dispatch -----


def test_kernel_dispatch_counters():
    from repro.kernels import ops

    reg = obs.MetricsRegistry()
    prev = obs.set_default_registry(reg)
    try:
        x = jax.random.normal(KEY, (4, 64))
        signs = np.where(np.arange(64) % 2 == 0, 1.0, -1.0).astype(np.float32)
        ops.hd_precondition(x, signs, mode="ref")
        ops.hd_precondition(x, signs, mode="ref")
        c = reg.counter("kernels.dispatch", op="hd_precondition", path="ref")
        assert c.value == 2
    finally:
        obs.set_default_registry(prev)


# ------------------------------------------------ spans of the ingest path --


def _ingest(blocks, n_shards=1):
    """fit_many on the first block, then partial_fit of each later one."""
    from repro.api import Plan, SparsifiedKMeans, SparsifiedPCA, fit_many

    plan = Plan(backend="stream", gamma=0.25, batch_size=32, cov_path="dense",
                n_shards=n_shards)
    km = SparsifiedKMeans(4, plan, key=5, algorithm="minibatch")
    pca = SparsifiedPCA(4, plan, key=5)
    run = fit_many(plan, [km, pca], blocks[0], finalize=False)
    for b in blocks[1:]:
        run.partial_fit(b)
    run.sync()
    return km, pca


def _host_spans(trace_dir, prefix):
    """(name, start, end, stats) of the host events named ``prefix*``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda h: (h[1], -h[2]))


def test_ingest_spans_in_a_cpu_profile(tmp_path):
    """fit_many plus two partial_fit calls under the profiler: one span tree
    per call, one chunk span per chunk, one compiled K-means step per chunk
    and no readback during ingest (the finalize reads the reassignment
    history once), one call identifier per call — and the same state as an
    untraced run."""
    rng = np.random.default_rng(3)
    blocks = [rng.normal(size=(64, 48)).astype(np.float32) for _ in range(3)]
    km0, pca0 = _ingest(blocks)
    km0.finalize()
    jax.profiler.start_trace(str(tmp_path))
    try:
        km, pca = _ingest(blocks)
        km.finalize()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path, "ingest.")

    top = "ingest.partial_fit"
    calls = [h for h in spans if h[0] == top]
    assert [h[3]["rows"] for h in calls] == [64, 64, 64]
    ids = [h[3]["call"] for h in calls]
    assert len(set(ids)) == 3
    per_call: dict = {}
    for name, s, e, st in spans:
        (owner,) = [c for c in calls if c[1] <= s and e <= c[2]]
        assert st["call"] == owner[3]["call"], name
        per_call.setdefault(owner[3]["call"], []).append(name)
    chunk = f"{top}.chunk"
    for i, c in enumerate(ids):
        names = per_call[c]
        want = {top: 1, f"{top}.h2d": 1, chunk: 2, f"{chunk}.sketch": 2,
                f"{chunk}.fold.kmeans": 2, f"{chunk}.fold.pca": 2,
                f"{chunk}.fold.kmeans.step": 2}
        if i == 0:
            want[f"{chunk}.fold.kmeans.init"] = 1
        assert {n: names.count(n) for n in set(names)} == want
    chunks = [h[3] for h in spans if h[0] == chunk]
    assert [st["chunk"] for st in chunks] == list(range(6))
    assert all(st["rows"] == 32 and st["shard"] == 0 for st in chunks)
    assert [h[3]["bytes"] for h in spans if h[0] == f"{top}.h2d"] == [64 * 48 * 4] * 3
    assert not [h for h in spans if h[0].endswith(".readback")]
    assert [h[3]["shards"] for h in spans if h[0] == f"{chunk}.fold.kmeans.step"] == [1] * 6
    readbacks = [h for h in _host_spans(tmp_path, "finalize.kmeans.readback")
                 if h[3]["site"] == "reassign_counts"]
    assert len(readbacks) == 1 and len(km.reassign_counts_) == 6
    np.testing.assert_array_equal(km.reassign_counts_, km0.reassign_counts_)
    assert [h[3]["consumer"] for h in spans if h[0] == f"{chunk}.fold.pca"] == [1] * 6

    for a, b in ((km0._km_state, km._km_state),
                 (pca0._reducer.state, pca._reducer.state)):
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n_shards", (1, 2))
def test_kmeans_step_is_one_launch(tmp_path, n_shards):
    """The host loop folds a minibatch K-means step as one compiled program:
    each ``fold.kmeans`` span after the seeding one launches at most one
    executable (the step, at the step's last shard; 84 eager launches
    before), under one ``fold.kmeans.step`` span per step."""
    rng = np.random.default_rng(5)
    blocks = [rng.normal(size=(64, 48)).astype(np.float32) for _ in range(3)]
    _ingest(blocks, n_shards)            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _ingest(blocks, n_shards)
    finally:
        jax.profiler.stop_trace()
    folds = _host_spans(tmp_path, "ingest.partial_fit.chunk.fold.kmeans")
    launches = [h for h in _host_spans(tmp_path, "PjRtCpuExecutable::Execute")
                if h[0] == "PjRtCpuExecutable::Execute"]

    def inside(span):
        return sum(1 for _, s, e, _ in launches if span[1] <= s and e <= span[2])

    spans = [h for h in folds if h[0].endswith("fold.kmeans")]
    steps = [h for h in folds if h[0].endswith("fold.kmeans.step")]
    assert len(spans) == 6 and len(steps) == 6 // n_shards
    assert [h[3]["shards"] for h in steps] == [n_shards] * len(steps)
    counts = [inside(h) for h in spans[1:]]  # the first also seeds (kmeans_init)
    assert all(c <= 2 for c in counts), counts
    # a step's earlier shards only buffer their sketch; its last launches it
    last = [i % n_shards == n_shards - 1 for i in range(1, 6)]
    assert [c for c, is_last in zip(counts, last) if not is_last] == [0] * last.count(False)
    assert all(c >= 1 for c, is_last in zip(counts, last) if is_last)
    assert all(inside(h) == 1 for h in steps)


def test_ingest_writes_no_span_series_without_a_registry():
    reg = obs.MetricsRegistry()
    prev = obs.set_default_registry(reg)
    try:
        rng = np.random.default_rng(4)
        km, pca = _ingest([rng.normal(size=(64, 48)).astype(np.float32)] * 2)
        km.finalize()
        pca.finalize()
    finally:
        obs.set_default_registry(prev)
    assert not [m for m in reg.metrics() if m.name == obs.tracing.SPAN_METRIC]
