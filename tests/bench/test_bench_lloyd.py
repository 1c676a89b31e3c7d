"""The ``kmeans_mnist8m_k256.dataset`` cell at rehearsal size, and its readers.

On the CPU, with the Pallas kernels in interpret mode: a sound run is
correct; the control (the reference one precision below the stated one, in
the program's place) is not; nor is a run with any of the faults planted
here under the timed path:

- ``skipped_iteration``: the second Lloyd iteration leaves the state as it
  found it, so the job runs one iteration short;
- ``moved_seed``: one K-means++ seed of every hypothesis is moved to the
  next retained row (its center with it);
- ``stale_labels``: ``labels_`` come from the centers that entered the last
  iteration, not the final ones;
- ``dropped_chunk``: the third chunk each retained sketch is handed is not
  kept.

Then the cell's five per-layer readers on a trace recorded on a TPU v5e
(``data/small_trace_lloyd``: a traced run of the cell's job cut to 16,384
rows, K = 16 and 3 iterations), beside the trace of a program without the
Lloyd programs or the finalize spans (``data/small_trace``), where each
reports nothing.
"""
import contextlib
import dataclasses
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import trace as T  # noqa: E402

CELL = "kmeans_mnist8m_k256.dataset"
DATA = Path(__file__).resolve().parent / "data"
READERS = ("lloyd.device_ms_per_iter", "lloyd_roofline", "kmeans.seed_device_ms",
           "finalize.share_pct", "finalize.idle_pct")
SEED = 7


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def skipped_iteration():
    from repro.core import kmeans as km

    real, calls = km.lloyd_step, [0]

    def step(vt, it, state, *a, **kw):
        calls[0] += 1
        return state if calls[0] % kw["max_iter"] == 2 else real(vt, it, state, *a, **kw)

    with patched(km, "lloyd_step", step):
        yield


@contextlib.contextmanager
def moved_seed():
    from repro.core import kmeans as km

    real = km.kmeans_seed

    def seed(vt, it, keys, **kw):
        centers, seeds = real(vt, it, keys, **kw)
        j = kw["k"] // 2
        moved = (seeds[:, j] + 1) % kw["n"]
        rows = km._rows_dense(vt, it, moved, kw["p"])
        return centers.at[:, j].set(rows), seeds.at[:, j].set(moved)

    with patched(km, "kmeans_seed", seed):
        yield


@contextlib.contextmanager
def stale_labels():
    from repro.core import kmeans as km

    real = km.lloyd_labels

    def labels(vt, it, state, seeds, **kw):
        fit = real(vt, it, state, seeds, **kw)
        stale = real(vt, it, dataclasses.replace(state, centers=state.prev), seeds, **kw)
        return dataclasses.replace(fit, labels=stale.labels)

    with patched(km, "lloyd_labels", labels):
        yield


@contextlib.contextmanager
def dropped_chunk():
    from repro.api import estimators

    real = estimators.RetainedSketch.append

    def append(self, s):
        self.handed = getattr(self, "handed", 0) + 1
        if self.handed != 3:
            real(self, s)

    with patched(estimators.RetainedSketch, "append", append):
        yield


FAULTS = {"skipped_iteration": skipped_iteration, "moved_seed": moved_seed,
          "stale_labels": stale_labels, "dropped_chunk": dropped_chunk}


def rehearse(fault=None, control=False):
    import jax

    from bench import run as RUN

    api = H.import_program()
    ctx = FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return RUN.run_cell(H, api, jax, H.workload(CELL), SEED, 0.0, rehearse=True,
                            control=control)


@pytest.fixture(scope="module")
def sound():
    return rehearse(control=True)


def test_rehearsal_is_correct(sound):
    res = sound["result"]
    assert res["correct"] is True, res["checks"]
    assert sound["diag"]["compiles_in_window"] == 0
    out = sound["diag"]["dispatch"]
    assert {k.split("/")[0] for k in out} >= {"kmeans_dists", "kmeans_assign", "kmeans_update"}
    # the job runs every iteration, so a skipped one or stale labels show
    cfg = H.merged(H.config("kmeans_mnist8m_k256"), True)
    assert res["checks"]["lloyd.n_iter"][0] == 0
    assert cfg["consumers"][0]["tol"] == 0.0


def test_control_is_not_correct(sound):
    from bench import compare as C

    limits = H.config("kmeans_mnist8m_k256")["limits"]
    ok, checks = C.judge(sound["diag"]["control"], limits)
    assert ok is False, checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault):
    res = rehearse(fault)["result"]
    assert res["correct"] is False, res["checks"]
    assert any(not (v <= lim) for v, lim in res["checks"].values()), res["checks"]


# ---------------------------------------------------------------- readers --

MS = 1_000_000          # ns
# a hand-made window, in ms: the sketch 0-8, the seeding program 10-30, three
# Lloyd iterations of 10 each with 2 idle between, the labels 64-66; the
# finalize span 9-70 with its seeding, iterations and labels' readback
HAND_OPS = [("sketch_fused", "jit__sketch_impl(1)", 0, 8), ("fusion", "jit_kmeans_seed(2)", 10, 30),
            ("assign", "jit_lloyd_step(3)", 30, 40), ("update", "jit_lloyd_step(3)", 42, 52),
            ("assign", "jit_lloyd_step(3)", 54, 64), ("assign", "jit_lloyd_labels(4)", 64, 66)]
HAND_HOST = [("finalize.kmeans", 9, 70), ("finalize.kmeans.seed", 9, 10),
             ("finalize.kmeans.lloyd", 10, 11), ("finalize.kmeans.readback", 11, 70),
             ("ingest.partial_fit", 0, 9)]


def hand_ctx(ops=HAND_OPS, host=HAND_HOST):
    tr = T.Trace({0: [T.Op(n, mod, s * MS, e * MS) for n, mod, s, e in ops]},
                 [(n, s * MS, e * MS) for n, s, e in host], 0, 100 * MS)
    traced = dict(RECORDED, lloyd_steps=3)
    return ctx_of(None, traced, trace=tr)


def test_readers_on_a_hand_made_window():
    ctx = hand_ctx()
    read = {n: H.metric_reader(n).read(ctx) for n in READERS}
    assert read["lloyd.device_ms_per_iter"]["value"] == pytest.approx(10.0)
    assert read["kmeans.seed_device_ms"]["value"] == pytest.approx(20.0 / 3)
    assert read["kmeans.seed_device_ms"]["per_step"] == pytest.approx(20.0 / 15)
    assert read["finalize.share_pct"]["value"] == pytest.approx(61.0)
    assert read["finalize.share_pct"]["per_span"]["finalize.kmeans.readback"] == pytest.approx(59.0)
    # idle inside the finalize: 9-10, 40-42, 52-54 and 66-70
    assert read["finalize.idle_pct"]["value"] == pytest.approx(100.0 * 9 / 61)
    assert read["lloyd_roofline"]["bound"] == "memory"   # K = 16: the sketch read bounds it


def test_hand_made_readers_report_nothing_without_their_parts():
    bare = hand_ctx(ops=HAND_OPS[:1], host=HAND_HOST[-1:])
    assert all(H.metric_reader(n).read(bare) is None for n in READERS)


# what the recorded run's window held (its diag): 16,384 rows in 4 chunks,
# 3 iterations, K = 16 (15 seeding steps), 3 hypotheses
RECORDED = {"chunks": 4, "steps": 4, "calls": 2, "lloyd_steps": 3, "seed_steps": 15,
            "hypotheses": 3}


def ctx_of(path, traced=RECORDED, trace=None):
    tr = trace or T.load(str(path))
    job = types.SimpleNamespace(
        m=51, p_pad=1024, batch=4096,
        cfg={"consumers": [{"kind": "kmeans_lloyd", "k": 16, "n_init": 3, "max_iter": 3,
                            "tol": 0.0}]})
    window = types.SimpleNamespace(traced=traced, rows=16384)
    return types.SimpleNamespace(trace=tr, job=job, window=window, chunks=traced["chunks"],
                                 devices=sorted(tr.devices)[:1],
                                 peaks=H.peaks("TPU v5 lite"))


# what the run that recorded the trace printed for each reader, on the chip
RECORDED_READS = {"lloyd.device_ms_per_iter": 1.069249, "lloyd_roofline": 0.763338817618036,
                  "kmeans.seed_device_ms": 2.4453563333333332,
                  "finalize.share_pct": 69.42290713037539, "finalize.idle_pct": 2.262428609105779}


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_recorded_trace(name):
    got = H.metric_reader(name).read(ctx_of(DATA / "small_trace_lloyd"))
    assert got is not None and got["value"] > 0, got
    assert got["value"] == pytest.approx(RECORDED_READS[name], rel=1e-6)
    if name.endswith("_pct") or name.endswith("roofline"):
        assert got["value"] < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_report_nothing_without_the_program(name):
    assert H.metric_reader(name).read(ctx_of(DATA / "small_trace")) is None


def test_per_iteration_and_roofline_agree():
    ctx = ctx_of(DATA / "small_trace_lloyd")
    per_iter = H.metric_reader("lloyd.device_ms_per_iter").read(ctx)["value"]
    ops, nbytes = H.consumer("kmeans_lloyd").fold_work(ctx.job.cfg["consumers"][0],
                                                       {"n": 16384, "m": 51})
    roof = H.metric_reader("lloyd_roofline").read(ctx)
    peak = ctx.peaks
    want = 100 * max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) / (per_iter / 1e3)
    assert roof["value"] == pytest.approx(want)
    assert ops == 3 * 3 * 16384 * 51 * 16
