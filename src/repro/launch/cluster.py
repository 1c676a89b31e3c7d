"""Multi-process streaming launcher: ``python -m repro.launch.cluster``.

The multi-host twin of ``repro.launch.stream``: N REAL OS processes bring up
``jax.distributed`` (gloo collectives on CPU), build the process-contiguous
mesh (``repro.cluster.process_mesh``), and fold the same (seed, step, shard)
stream — each process generates ONLY the shards it owns, the per-step psum is
the only cross-process traffic. With ``--ckpt-dir`` the run checkpoints its
EngineState periodically (process 0 writes) and ``--resume`` continues from
the latest checkpoint bit-identically.

Run it twice to see fault tolerance end to end::

    # 2 processes, 2 shards, checkpoint every 5 steps — kill it mid-run
    PYTHONPATH=src python -m repro.launch.cluster --nproc 2 --steps 20 \\
        --ckpt-dir /tmp/ck --ckpt-every 5

    # resume from the latest checkpoint and finish the same 20 steps
    PYTHONPATH=src python -m repro.launch.cluster --nproc 2 --steps 20 \\
        --ckpt-dir /tmp/ck --resume

Without ``--process-id`` the command is the COORDINATOR: it picks a free port
and spawns ``--nproc`` copies of itself as workers (the single-machine path;
on a real cluster start one worker per host with ``--process-id``/
``--coordinator`` set explicitly and skip the self-spawn).

The self-spawn is a CPU multi-process rehearsal: it starts N processes on one
host, and a TPU belongs to one process at a time, so do not run it on a chip
host. Run it with ``JAX_PLATFORMS=cpu``; the workers inherit the caller's
platform.
"""
from __future__ import annotations

import argparse
import socket
import subprocess
import sys
import time


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nproc", type=int, default=2, help="number of processes")
    ap.add_argument("--process-id", type=int, default=None,
                    help="worker mode: this process's id (coordinator spawns these)")
    ap.add_argument("--coordinator", type=str, default=None,
                    help="host:port of process 0 (worker mode)")
    ap.add_argument("--p", type=int, default=1024)
    ap.add_argument("--gamma", type=float, default=0.1)
    ap.add_argument("--batch", type=int, default=256, help="rows per shard per step")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--shards", type=int, default=0,
                    help="logical shards (default: nproc, one per process)")
    ap.add_argument("--kmeans-k", type=int, default=0, help="0 disables K-means")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint the EngineState every N steps")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--log-every", type=int, default=0,
                    help="every N steps: process 0 emits a structured JSONL "
                         "progress record and publishes the merged cluster "
                         "heartbeat (0 = telemetry off)")
    return ap


def _spawn(args) -> int:
    """Coordinator: free port, one worker subprocess per process id."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cmd = [sys.executable, "-m", "repro.launch.cluster",
           "--coordinator", f"127.0.0.1:{port}"]
    for flag in ("nproc", "p", "batch", "steps", "shards", "kmeans_k", "seed",
                 "ckpt_every", "log_every"):
        cmd += [f"--{flag.replace('_', '-')}", str(getattr(args, flag))]
    cmd += ["--gamma", str(args.gamma)]
    if args.ckpt_dir:
        cmd += ["--ckpt-dir", args.ckpt_dir]
    if args.resume:
        cmd += ["--resume"]
    procs = [subprocess.Popen(cmd + ["--process-id", str(pid)])
             for pid in range(args.nproc)]
    rc = 0
    for p in procs:
        rc = rc or p.wait()
    return rc


def _worker(args) -> int:
    from repro import cluster
    from repro.launch import compile_cache

    compile_cache.enable()
    cluster.initialize(args.coordinator, args.nproc, args.process_id)

    import jax

    from repro import api
    from repro.data.pipeline import VectorStreamSource
    from repro.stream import StreamKMeansConfig

    shards = args.shards or args.nproc
    plan = api.Plan(backend="sharded", gamma=args.gamma,
                    batch_size=args.batch, n_shards=shards)
    source = VectorStreamSource(p=args.p, batch=args.batch, seed=args.seed)
    km = StreamKMeansConfig(k=args.kmeans_k) if args.kmeans_k else None
    engine = api.make_engine(plan, args.p, jax.random.PRNGKey(args.seed + 1),
                             source, kmeans=km)

    state, start = None, 0
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume needs --ckpt-dir")
        state, start = engine.restore_state(args.ckpt_dir)

    tel = None
    if args.log_every:
        from repro import obs
        from repro.stream import EngineTelemetry

        reg = obs.MetricsRegistry()
        log_every = args.log_every

        def _on_step(rec, _reg=reg):
            # every process stamps + gathers at the SAME steps (the heartbeat
            # allgather is a collective — the condition must be symmetric);
            # process 0 publishes the merged view as cluster.* gauges
            if (rec["step"] + 1) % log_every:
                return
            hb = cluster.beat(rec["step"] + 1, rows=rec["rows_total"])
            cluster.publish_local(hb, registry=_reg)
            view = cluster.gather(hb)
            if jax.process_index() == 0:
                cluster.publish(view, registry=_reg)

        logger = (obs.StepLogger(stream=sys.stderr,
                                 static={"p": args.p, "shards": shards,
                                         "nproc": args.nproc})
                  if jax.process_index() == 0 else None)
        tel = EngineTelemetry(registry=reg, step_logger=logger,
                              log_every=log_every, on_step=_on_step)

    t0 = time.time()
    res = engine.run(args.steps, seed=args.seed, state=state, start_step=start,
                     checkpoint_dir=args.ckpt_dir,
                     checkpoint_every=args.ckpt_every if args.ckpt_dir else 0,
                     telemetry=tel)
    jax.block_until_ready(res.mean)
    dt = time.time() - t0

    if jax.process_index() == 0:
        rows = int(res.count)
        folded = (args.steps - start) * shards * args.batch
        print(f"p={args.p} gamma={engine.spec.gamma:.3f} shards={shards} "
              f"processes={jax.process_count()} "
              f"(this run folded steps {start}..{args.steps - 1})")
        print(f"total rows in state: {rows:,}; folded {folded:,} rows in "
              f"{dt:.2f}s ({folded / dt:,.0f} rows/s incl. compile)")
        print(f"mean[:4] = {[round(float(v), 4) for v in res.mean[:4]]}")
        if res.centers is not None:
            print(f"kmeans: K={args.kmeans_k}, "
                  f"best accumulated obj = {float(res.kmeans_obj):.2f}")
        if tel is not None:
            hbv = {m.name: m.value for m in tel.registry.metrics()
                   if m.name.startswith("cluster.") and not m.labels}
            if hbv:
                print(f"heartbeat: hosts={hbv.get('cluster.hosts', 0):.0f} "
                      f"step={hbv.get('cluster.step', 0):.0f} "
                      f"straggler_lag={hbv.get('cluster.straggler_lag_s', 0):.3f}s")
    return 0


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    if args.process_id is None:
        return _spawn(args)
    if not args.coordinator:
        raise SystemExit("worker mode (--process-id) needs --coordinator")
    return _worker(args)


if __name__ == "__main__":
    raise SystemExit(main())
