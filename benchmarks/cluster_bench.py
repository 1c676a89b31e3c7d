"""EngineState lifecycle benchmark → ``BENCH_cluster.json``.

Three costs of the fault-tolerance / multi-host story, each measured and the
correctness condition behind it asserted:

1. **Checkpoint/restore overhead** — a run with ``checkpoint_every`` vs the
   uninterrupted run (overhead fraction), plus save/restore wall time for the
   fixed-size EngineState. Restore-and-continue must be BIT-identical to the
   uninterrupted run (the (seed, step, shard) contract regenerates the rest).
2. **Elastic re-shard replay cost** — finishing a restored 8-shard run under
   4 and 2 simulated workers (``cluster.continue_elastic``): per-step wall
   time vs the engine's own per-step time. Final moments must match the
   uninterrupted run at 1e-5 (delta merge = float-sum reordering only).
3. **Multi-process vs single-process rows/sec** — the same sharded fit run by
   2 REAL processes (gloo CPU collectives, ``jax.distributed``) vs one
   process with 2 forced host devices. On CPU gloo adds transport cost; the
   row records the achieved fraction so the trajectory is visible across
   commits. Results must agree at 1e-5.

CI uploads the JSON as an artifact (same convention as ``BENCH_api.json``).

This is a CPU multi-process rehearsal: it imports JAX and then spawns CPU
child processes (``JAX_PLATFORMS=cpu``), and a TPU belongs to one process at
a time, so do not run it on a chip host. Its times are CPU wall clocks.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.cluster import continue_elastic
from repro.core import sketch as sketch_mod
from repro.stream.engine import StreamEngine, StreamKMeansConfig

RECORDS: list[dict] = []

P_DIM = 256
B = 512
STEPS = 12
CKPT_EVERY = 4


def record(name: str, us: float, **extra):
    rec = {"name": name, "us_per_call": round(us, 1), **extra}
    RECORDS.append(rec)
    derived = " ".join(f"{k}={v}" for k, v in extra.items()
                       if isinstance(v, (int, float, str)))
    emit(name, us, derived)


def _source(seed, step, shard):
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed or 0), step), shard)
    return jax.random.normal(k, (B, P_DIM))


def _engine(n_shards: int) -> StreamEngine:
    spec = sketch_mod.make_spec(P_DIM, jax.random.PRNGKey(3), gamma=0.1)
    return StreamEngine(spec, _source, n_shards=n_shards,
                        kmeans=StreamKMeansConfig(4, n_init=2))


def checkpoint_restore_bench(ckpt_dir: str):
    eng = _engine(4)
    eng.run(1, seed=0)  # compile outside the timed region
    t0 = time.perf_counter()
    full = eng.run(STEPS, seed=0)
    t_plain = time.perf_counter() - t0

    eng2 = _engine(4)
    eng2.run(1, seed=0)
    t0 = time.perf_counter()
    eng2.run(STEPS, seed=0, checkpoint_dir=ckpt_dir,
             checkpoint_every=CKPT_EVERY)
    t_ckpt = time.perf_counter() - t0

    eng3 = _engine(4)
    eng3.run(1, seed=0)
    t0 = time.perf_counter()
    state, next_step = eng3.restore_state(ckpt_dir)
    t_restore = time.perf_counter() - t0
    res = eng3.run(STEPS, seed=0, state=state, start_step=next_step)
    assert np.array_equal(np.asarray(res.mean), np.asarray(full.mean)), (
        "restore-and-continue is not bit-identical to the uninterrupted run")
    assert np.array_equal(np.asarray(res.centers), np.asarray(full.centers))

    n_ckpts = STEPS // CKPT_EVERY
    rows = STEPS * 4 * B
    record("cluster/checkpoint/overhead", (t_ckpt - t_plain) * 1e6 / n_ckpts,
           overhead_frac=round(max(0.0, t_ckpt / t_plain - 1.0), 4),
           rows_per_sec=round(rows / t_ckpt),
           checkpoints=n_ckpts, bit_identical=True)
    record("cluster/checkpoint/restore", t_restore * 1e6,
           restore_ms=round(t_restore * 1e3, 2), resumed_at=next_step)


def elastic_reshard_bench(ckpt_dir: str):
    eng = _engine(8)
    full = eng.run(STEPS, seed=1)
    eng2 = _engine(8)
    eng2.run(STEPS // 2, seed=1)
    eng2.save_state(ckpt_dir, STEPS // 2, seed=1)

    # baseline: the engine's own per-step cost over the back half
    eng3 = _engine(8)
    eng3.run(1, seed=1)
    state, start = eng3.restore_state(ckpt_dir)
    t0 = time.perf_counter()
    eng3.run(STEPS, seed=1, state=state, start_step=start)
    t_engine = (time.perf_counter() - t0) / (STEPS - start)

    for n_workers in (4, 2):
        eng4 = _engine(8)
        eng4.run(1, seed=1)
        state, start = eng4.restore_state(ckpt_dir)
        t0 = time.perf_counter()
        continue_elastic(eng4, STEPS, state=state, start_step=start,
                         n_workers=n_workers, seed=1)
        t_step = (time.perf_counter() - t0) / (STEPS - start)
        res = eng4.finalize()
        np.testing.assert_allclose(np.asarray(res.mean), np.asarray(full.mean),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.cov), np.asarray(full.cov),
                                   atol=1e-5)
        record(f"cluster/elastic/8_to_{n_workers}", t_step * 1e6,
               vs_engine_step=round(t_step / t_engine, 2),
               rows_per_sec=round(8 * B / t_step), parity_atol=1e-5)


_MP_FIT = """
import sys, time, json
import numpy as np

MODE = sys.argv[1]
if MODE == "worker":
    pid, nproc, port = int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    from repro import cluster
    cluster.initialize(f"127.0.0.1:{port}", nproc, pid)
import jax
from repro.api import Plan, SparsifiedCov, fit_many

B, P, STEPS = 512, 256, 10

def source(seed, step, shard):
    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed or 0), step), shard)
    return jax.random.normal(k, (B, P))

plan = Plan(backend="sharded", gamma=0.1, batch_size=B, n_shards=2)
cov = SparsifiedCov(plan, key=3)
fit_many(plan, [cov], source=source, steps=1, seed=5)  # compile
cov2 = SparsifiedCov(plan, key=3)
t0 = time.perf_counter()
fit_many(plan, [cov2], source=source, steps=STEPS, seed=5)
dt = time.perf_counter() - t0
if MODE != "worker" or int(sys.argv[2]) == 0:
    print("RESULT" + json.dumps({
        "rows_per_sec": STEPS * 2 * B / dt,
        "mean": np.asarray(cov2.mean_).tolist(),
        "cov_tr": float(np.trace(np.asarray(cov2.cov_)))}))
"""


def multiprocess_bench():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu")
    script = textwrap.dedent(_MP_FIT)

    ref_env = dict(env, XLA_FLAGS="--xla_force_host_platform_device_count=2")
    t0 = time.perf_counter()
    ref_out = subprocess.run([sys.executable, "-c", script, "single"],
                             env=ref_env, capture_output=True, text=True,
                             timeout=600)
    assert ref_out.returncode == 0, ref_out.stderr[-4000:]
    ref = json.loads(ref_out.stdout.split("RESULT", 1)[1])

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with tempfile.TemporaryDirectory() as d:
        wpath = os.path.join(d, "w.py")
        with open(wpath, "w") as f:
            f.write(script)
        procs = [subprocess.Popen(
            [sys.executable, wpath, "worker", str(pid), "2", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for pid in range(2)]
        outs = [p.communicate(timeout=600) for p in procs]
    for p, (o, e) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{e[-4000:]}"
    got = json.loads(outs[0][0].split("RESULT", 1)[1])

    np.testing.assert_allclose(got["mean"], ref["mean"], atol=1e-5)
    np.testing.assert_allclose(got["cov_tr"], ref["cov_tr"], rtol=1e-5)
    record("cluster/multiprocess/2proc_vs_1proc",
           (time.perf_counter() - t0) * 1e6,
           rows_per_sec_2proc=round(got["rows_per_sec"]),
           rows_per_sec_1proc=round(ref["rows_per_sec"]),
           fraction=round(got["rows_per_sec"] / ref["rows_per_sec"], 3),
           parity_atol=1e-5)


def run(json_path: str = "BENCH_cluster.json"):
    RECORDS.clear()
    with tempfile.TemporaryDirectory() as d:
        checkpoint_restore_bench(os.path.join(d, "ck"))
        elastic_reshard_bench(os.path.join(d, "el"))
    multiprocess_bench()
    out = os.environ.get("BENCH_CLUSTER_JSON", json_path)
    with open(out, "w") as f:
        json.dump({"records": RECORDS}, f, indent=2)
    print(f"cluster_bench: wrote {out} ({len(RECORDS)} records)",
          file=sys.stderr)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
