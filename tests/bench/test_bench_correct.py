"""The comparison that decides ``correct`` fails the control and every fault.

At the rehearsal's sizes on the CPU, with the Pallas kernels in interpret
mode: a sound run of each one-chip cell is correct; the control (the
reference one precision below the configuration's, in the program's place)
is not; nor is a run with any fault of ``bench_faults`` planted under the
timed path. Each cell runs in one child process, in a copy of the
benchmark's files that holds the cells staged as files alone; the
four-chip cell runs on four host devices, for every fault of
``bench_faults``, the exchange's among them.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1])]

import bench_tree  # noqa: E402

ONE_CHIP = ["pca_p65536.stream", "kmeans_mnist784.stream"]
FAULTS = ["unchanged", "half_batch", "altered"]
SHARDED_FAULTS = ["unchanged", "half_batch", "altered", "no_exchange"]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{cell: {fault: line}} of every cell's sound run and faults."""
    root = bench_tree.tree(tmp_path_factory.mktemp("cells"))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    got = {}
    for cell, faults in [(c, ["sound"] + FAULTS) for c in ONE_CHIP] + [
            ("pca_p65536_x4.stream", SHARDED_FAULTS)]:
        out = subprocess.run([sys.executable, str(root / "tests/bench/bench_faults.py"), cell,
                              ",".join(faults)],
                             capture_output=True, text=True, env=env, timeout=600)
        assert out.returncode == 0, out.stderr[-3000:]
        lines = [json.loads(ln) for ln in out.stdout.strip().splitlines() if ln.startswith("{")]
        got[cell] = {ln["fault"]: ln for ln in lines}
    return got


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct_and_control_is_not(runs, cell):
    got = runs[cell]["sound"]
    assert got["correct"] is True, got["checks"]
    assert got["control_correct"] is False, got["control_checks"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_is_not_correct(runs, cell, fault):
    got = runs[cell][fault]
    assert got["correct"] is False, got
    assert any(not (v <= lim) for v, lim in got["checks"].values()), got


@pytest.mark.parametrize("fault", SHARDED_FAULTS)
def test_sharded_cell_fault_is_not_correct(runs, fault):
    got = runs["pca_p65536_x4.stream"][fault]
    assert got["correct"] is False, got
    assert any(not (v <= lim) for v, lim in got["checks"].values()), got
