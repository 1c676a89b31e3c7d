"""A copy of the benchmark's files with cells added to its manifest.

``BENCHMARK.json`` and ``bench/`` (and this directory) are copied into a
fresh directory, ``src`` is linked, and the staged cells and the given
configurations and workloads that the manifest lacks are appended to the
copy's: a cell that exists only as files, such as the four-chip
configuration that waits for its first chip measurement, runs there as the
harness would run it.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# the four-chip cell: its configuration and metric files are in bench/; it
# joins BENCHMARK.json once it has been measured on four chips
X4_CONFIG = {"name": "pca_p65536_x4",
             "source": "https://arxiv.org/abs/1511.00152 Tables III/IV (streaming PCA, gamma = 0.05)",
             "file": "bench/configs/pca_p65536_x4.json", "reduced": [],
             "why": "pca_p65536 with its rows sharded over a 2x2 mesh"}
X4_CELL = {"name": "pca_p65536_x4.stream", "config": "pca_p65536_x4", "traffic": "stream",
           "chips": 4, "why": "one step of 4 x 2048 rows per partial_fit, one psum per step"}


# the one-chip PCA cell: its finalized eigenpairs have no limit that parts
# the program from its control yet, so it waits beside the four-chip one
PCA_CONFIG = {"name": "pca_p65536",
              "source": "https://arxiv.org/abs/1511.00152 Tables III/IV (streaming PCA, gamma = 0.05)",
              "file": "bench/configs/pca_p65536.json", "reduced": [],
              "why": "dense streaming PCA at p = 2^16 on the low-rank range path"}
PCA_CELL = {"name": "pca_p65536.stream", "config": "pca_p65536", "traffic": "stream",
            "chips": 1, "why": "2048-row host blocks, one partial_fit each, closed loop"}

STAGED_CONFIGS = [PCA_CONFIG, X4_CONFIG]
STAGED_CELLS = [PCA_CELL, X4_CELL]


def tree(dst: Path, configs=(), workloads=()) -> Path:
    for name in ("BENCHMARK.json", "bench", "tests/bench"):
        src, out = ROOT / name, dst / name
        out.parent.mkdir(parents=True, exist_ok=True)
        if src.is_dir():
            shutil.copytree(src, out, ignore=shutil.ignore_patterns("__pycache__", "data"))
        else:
            shutil.copy(src, out)
    (dst / "src").symlink_to(ROOT / "src")
    man = json.loads((dst / "BENCHMARK.json").read_text())
    for key, extra in (("configs", STAGED_CONFIGS + list(configs)),
                       ("workloads", STAGED_CELLS + list(workloads))):
        have = {e["name"] for e in man[key]}
        man[key] += [e for e in extra if e["name"] not in have]
    (dst / "BENCHMARK.json").write_text(json.dumps(man))
    return dst
