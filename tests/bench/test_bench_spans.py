"""The readers of the program's own spans: ``api.h2d_ms_per_chunk``,
``api.dispatch_ms_per_chunk``, ``api.readback_ms_per_chunk`` and
``device.idle_in_api_pct`` (``bench/spans.py``).

Unit cases on hand-made traces (nested self time, clipping to the window,
idle time under a span, a program without spans), and the four readers on a
small trace recorded on a TPU v5e with the program's spans
(``data/small_trace_spans``: a ``--seconds 0`` traced run of
``kmeans_mnist784.stream``, whose window holds one call of two chunks),
beside the trace of a program without them (``data/small_trace``).
"""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import spans as S  # noqa: E402
from bench import trace as T  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
READERS = ("api.h2d_ms_per_chunk", "api.dispatch_ms_per_chunk",
           "api.readback_ms_per_chunk", "device.idle_in_api_pct")
MS = 1_000_000          # ns
CALL = "ingest.partial_fit"
CHUNK = CALL + ".chunk"
# one call of one chunk, in ms: h2d 10, readback 15, sketch 10, fold.kmeans
# 25 (its init 5 with it), fold.pca 25, and 15 of the call's and chunk's own
HOST = [(CALL, 0, 100), (CALL + ".h2d", 0, 10), (CHUNK, 10, 90),
        (CHUNK + ".sketch", 10, 20), (CHUNK + ".fold.kmeans", 20, 60),
        (CHUNK + ".fold.kmeans.init", 20, 25),
        (CHUNK + ".fold.kmeans.readback", 40, 55), (CHUNK + ".fold.pca", 60, 85),
        ("bench.partial_fit", 0, 100), ("PjitFunction(add)", 22, 23)]


def trace(host=HOST, busy=((0, 30), (50, 100)), t0=0, t1=100):
    ops = [T.Op("fusion", "jit_f", s * MS, e * MS) for s, e in busy]
    return T.Trace({0: ops}, [(n, s * MS, e * MS) for n, s, e in host], t0 * MS, t1 * MS)


def read(name, tr, chunks=1, devices=(0,)):
    ctx = types.SimpleNamespace(trace=tr, chunks=chunks, devices=list(devices))
    return H.metric_reader(name).read(ctx)


def test_nested_self_time_adds_up_to_the_call():
    h2d, disp, rb = (read(n, trace())["value"] for n in READERS[:3])
    assert (h2d, rb) == (10, 15)
    assert read("api.dispatch_ms_per_chunk", trace())["per_span"] == {
        "fold.kmeans": 25, "fold.pca": 25, "other": 15, "sketch": 10}
    assert read("api.readback_ms_per_chunk", trace())["per_site"] == {"fold.kmeans": 15}
    assert h2d + disp + rb == 100
    assert read("api.h2d_ms_per_chunk", trace(), chunks=4)["value"] == 2.5


def test_spans_are_clipped_to_the_window():
    tr = trace(t0=5, t1=95)
    assert read("api.h2d_ms_per_chunk", tr)["value"] == 5
    assert read("api.dispatch_ms_per_chunk", tr)["per_span"]["other"] == 10
    assert sum(e - s for _, s, e in S.segments(tr)) == 90 * MS


def test_idle_time_under_a_span():
    # the device idles over 30..50 inside the call and 100..120 outside it
    tr = trace(t1=120)
    got = read("device.idle_in_api_pct", tr)
    assert got["per_span"] == pytest.approx({CHUNK + ".fold.kmeans": 100 * 10 / 120,
                                             CHUNK + ".fold.kmeans.readback": 100 * 10 / 120})
    assert got["value"] == pytest.approx(100 * 20 / 120)
    idle = H.metric_reader("device.idle_pct").read(
        types.SimpleNamespace(trace=tr, devices=[0]))["value"]
    assert idle == pytest.approx(100 * 40 / 120) and got["value"] <= idle


@pytest.mark.parametrize("name", READERS)
def test_nothing_without_spans_or_devices(name):
    no_spans = trace(host=[h for h in HOST if not h[0].startswith("ingest.")])
    assert read(name, no_spans) is None
    assert read(name, trace(), devices=()) is None
    assert read(name, trace(), chunks=0) is None


@pytest.fixture(scope="module")
def recorded():
    return T.load(str(DATA / "small_trace_spans"))


def _chunks(tr):
    return sum(1 for n, s, e in tr.host if n == CHUNK and tr.t0 <= s and e <= tr.t1)


def test_recorded_trace_with_spans(recorded):
    tr = recorded
    chunks = _chunks(tr)
    assert chunks == 2 and sorted(tr.devices) == [0]
    got = {n: read(n, tr, chunks) for n in READERS}
    assert all(g is not None for g in got.values())
    calls = [(s, e) for n, s, e in tr.host if n == CALL and tr.t0 <= s and e <= tr.t1]
    own = sum(e - s for s, e in calls) / 1e6 / chunks
    host = sum(got[n]["value"] for n in READERS[:3])
    assert host == pytest.approx(own, rel=1e-9)
    per = got["api.dispatch_ms_per_chunk"]["per_span"]
    assert {"sketch", "fold.kmeans", "fold.pca", "other"} <= set(per)
    assert sum(per.values()) == pytest.approx(got["api.dispatch_ms_per_chunk"]["value"])
    # the benchmark's clock around each call sees the program's own time
    bench = sum(e - s for n, s, e in tr.host
                if n == "bench.partial_fit" and tr.t0 <= s and e <= tr.t1) / 1e6 / chunks
    assert own <= bench and own == pytest.approx(bench, rel=0.1)
    idle = H.metric_reader("device.idle_pct").read(
        types.SimpleNamespace(trace=tr, devices=[0]))["value"]
    assert 0 < got["device.idle_in_api_pct"]["value"] <= idle


@pytest.mark.parametrize("name", READERS)
def test_recorded_trace_without_spans(name):
    tr = T.load(str(DATA / "small_trace"))
    assert read(name, tr, chunks=4) is None
