"""The peaks table and the essential-work functions, against hand counts.

The work of a chunk is counted from its shapes (n rows of width p, padded
to p_pad, m kept), at the three configurations' shapes: p = 65536,
n = 2048, m = 3277, l = 128 for both PCA configurations (per chunk, so the
four-chip one's is the one-chip one's), and p = 784 → 1024, n = 4096,
m = 51, K = 10 over 3 hypotheses for the MNIST-shaped one. Each is read
from its file, ``bench/configs/<name>.json``.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import data, harness as H  # noqa: E402


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def shape(name):
    cfg = config(name)
    p = data.width(cfg["data"])
    job = H.Job(cfg, H.mix("stream"), 0, np.zeros((1, p), np.float32),
                int(cfg["plan"]["batch_size"]), None)
    return H.metric_reader("sketch_roofline").shape_of(job)


PCA_SKETCH = (2048 * 65536 * 16, 2048 * 65536 * 4 + 2048 * 3277 * 8)
PCA_FOLD = (4 * 2048 * 3277 * 128, 2048 * 3277 * 8 + 3 * 65536 * 128 * 4)
KM_SKETCH = (4096 * 1024 * 10, 4096 * 784 * 4 + 4096 * 51 * 8)
KM_FOLD = (2 * 4096 * 51 * 51 + 2 * 3 * 3 * 4096 * 51 * 10,
           4096 * 51 * 8 + 2 * 1024 * 1024 * 4 + 2 * 3 * 10 * 1024 * 8)


@pytest.mark.parametrize("name,sketch,fold", [
    ("pca_p65536", PCA_SKETCH, PCA_FOLD),
    ("pca_p65536_x4", PCA_SKETCH, PCA_FOLD),
    ("kmeans_mnist784", KM_SKETCH, KM_FOLD),
])
def test_essential_work_at_the_cells_shapes(name, sketch, fold):
    s = shape(name)
    assert H.metric_reader("sketch_roofline").work(s) == tuple(map(float, sketch))
    assert H.metric_reader("fold_roofline").work(s) == tuple(map(float, fold))


def test_peaks_table():
    v5e = H.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        H.peaks("TPU v9 imaginary")


def test_schedule_of_the_stream_mix():
    mx = H.mix("stream")
    # 2048-row blocks of 512 MiB from a 2 GiB pool; 8192-row blocks of two
    # chunks from 65,536 rows; one 4 x 2048-row step per call
    for name, want in [("pca_p65536", (2048, 4)), ("kmeans_mnist784", (8192, 8)),
                       ("pca_p65536_x4", (8192, 1))]:
        cfg = config(name)
        assert H.feed(mx["feed"]).schedule(cfg, mx, data.width(cfg["data"])) == want
