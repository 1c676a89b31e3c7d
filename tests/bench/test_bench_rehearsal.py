"""The CPU rehearsal of every cell, and a cell added by files alone.

``bench/run.py --rehearse`` runs a cell's ``rehearse`` shapes on the CPU
(kernels in interpret mode, four host devices for a four-chip cell) and
prints a ``rehearsal`` line, never a metric. A new configuration and mix,
added as files with a ``workloads`` entry, run with no edit to any file
the benchmark already has.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_tree  # noqa: E402

ROOT = HERE.parents[1]
CELLS = sorted({w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
               | {c["name"] for c in bench_tree.STAGED_CELLS})
KEYS = {"rehearsal", "correct", "attempted", "device", "checks"}


def rehearse(root: Path, cell: str):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run([sys.executable, str(root / "bench" / "run.py"), "--workload", cell,
                          "--rehearse", "--seed", "3000000017"],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_no_metric(cell, tmp_path):
    root = bench_tree.tree(tmp_path)
    line, err = rehearse(root, cell)
    assert set(line) == KEYS
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert "metrics" not in line and "rows_per_s" not in json.dumps(line)
    # the compared numbers close standard error, each beside its limit
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


def _add_cell(root: Path, name: str, config: str, traffic: str):
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1,
                             "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def _write(path: Path, text: str):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


# mixes of the Open questions' later cells: the data file alone changes
DATA_ONLY_MIXES = {
    "scan": ("kmeans_mnist784", {"scan": True}),
    "device_rows": ("pca_p65536", {"rows_on": "device"}),
    "short": ("kmeans_mnist784", {"rehearse": {"min_call_bytes": 1, "pool_rows": 96,
                                               "pool_bytes": 1 << 30, "min_calls": 2}}),
}


@pytest.mark.parametrize("traffic", sorted(DATA_ONLY_MIXES))
def test_new_cell_is_found_by_name(traffic, tmp_path):
    """A new mix (and, for ``short``, a new configuration) as data files and a
    ``workloads`` entry, with no other file touched."""
    root = bench_tree.tree(tmp_path)
    config, extra = DATA_ONLY_MIXES[traffic]
    if traffic == "short":
        cfg = json.loads((ROOT / f"bench/configs/{config}.json").read_text())
        config = cfg["name"] = "kmeans_small"
        _write(root / "bench/configs/kmeans_small.json", json.dumps(cfg))
        man = json.loads((root / "BENCHMARK.json").read_text())
        man["configs"].append({"name": "kmeans_small", "source": "test", "reduced": [],
                               "file": "bench/configs/kmeans_small.json", "why": "test"})
        (root / "BENCHMARK.json").write_text(json.dumps(man))
    mix = {**json.loads((ROOT / "bench/mixes/stream.json").read_text()), **extra}
    _write(root / f"bench/mixes/{traffic}.json", json.dumps(mix))
    _add_cell(root, f"{config}.{traffic}", config, traffic)
    line, _ = rehearse(root, f"{config}.{traffic}")
    assert line["correct"] is True
    assert line["attempted"] == (2 if traffic == "short" else 3)
    # a scan keeps no sketch: its run compares the folded state and outputs
    assert ("sketch.values" in line["checks"]) == (traffic != "scan")


FEED = """
from bench import harness as H

_blocks = H.feed("blocks")


def schedule(cfg, mx, p):
    return int(cfg["plan"]["batch_size"]) * int(mx["chunks_per_call"]), int(mx["calls"])


warm_up = _blocks.warm_up


def run_window(api, job, impl, seconds, blocks, **kw):
    return _blocks.run_window(api, job, impl, 0.0, blocks, **kw)
"""

ROWS = """
import jax


def width(d):
    return int(d["p"])


def block(keys, n, d):
    return jax.random.uniform(keys[1], (n, int(d["p"])))
"""


def test_new_feed_and_rows_kind_are_found_by_name(tmp_path):
    """A mix whose feed is a new file, over rows of a kind that is a new
    file: both found by the names in the data files."""
    root = bench_tree.tree(tmp_path)
    _write(root / "bench/feeds/fixed_calls.py", FEED)
    _write(root / "bench/rows/uniform.py", ROWS)
    _write(root / "bench/mixes/fixed.json",
           json.dumps({"feed": "fixed_calls", "chunks_per_call": 3, "calls": 2,
                       "min_calls": 4}))
    cfg = json.loads((ROOT / "bench/configs/kmeans_mnist784.json").read_text())
    cfg.update(name="kmeans_uniform", data={"kind": "uniform", "p": 200})
    cfg.pop("rehearse")
    cfg["plan"]["batch_size"] = 16
    cfg["consumers"] = [{"kind": "kmeans", "k": 3, "algorithm": "minibatch", "n_init": 2}]
    cfg["limits"] = {k: v for k, v in cfg["limits"].items() if not k.startswith("pca.")}
    _write(root / "bench/configs/kmeans_uniform.json", json.dumps(cfg))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "kmeans_uniform", "source": "test", "reduced": [],
                           "file": "bench/configs/kmeans_uniform.json", "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    _add_cell(root, "kmeans_uniform.fixed", "kmeans_uniform", "fixed")
    line, _ = rehearse(root, "kmeans_uniform.fixed")
    assert line["correct"] is True
    assert line["attempted"] == 4 * 3          # four calls of three chunks
