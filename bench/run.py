#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --rehearse     # tiny shapes on the CPU

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``). Set-up draws the cell's rows
on the device from ``--seed`` into a host pool, warms every program the
window uses (one block and one finalize of a separate run), and starts a
fresh run with its first block (``fit_many``). The window starts at that
run's first ``partial_fit``, folds pool blocks through the program's ingest
front door for ``--seconds``, and ends when the finalized outputs are on the
host. Afterwards the plain reference replays every chunk the window folded,
and the run is ``correct`` when each number of ``bench/compare.py`` lies
within the configuration's limit. ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
records the profiler over the window and prints its per-layer metrics.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and ``checks`` last: every number compared, as [reading, limit];
the same numbers close standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits 3 and prints no result. ``--rehearse``
runs the cell's ``rehearse`` shapes on the CPU with the Pallas kernels in
interpret mode (four host devices for a four-chip cell) and prints
``{"rehearsal": ...}``, never a metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the window (default: BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU, kernels in interpret mode")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's profile into this directory")
    return ap.parse_args(argv)


def prepare_env(rehearse: bool, chips: int) -> None:
    """Settings that must precede JAX's start."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={chips}"
        if chips > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    else:
        # the persistent compile cache lives at one fixed place in the checkout
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def device_info(jax, n: int) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d), "used": n}


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness as H

    man = H.manifest()
    wl = H.workload(args.workload, man)
    chips = int(wl["chips"])
    prepare_env(args.rehearse, chips)
    try:
        import jax

        api = H.import_program()
    except ImportError as e:
        print(f"bench: cannot import the program or JAX ({e})", file=sys.stderr)
        return 2
    devs = jax.devices()
    if not args.rehearse:
        if devs[0].platform != "tpu":
            print(f"bench: no TPU found (platform {devs[0].platform!r}); this benchmark "
                  "runs on the chip only (--rehearse for the CPU rehearsal)", file=sys.stderr)
            return 3
        if len(devs) < chips:
            print(f"bench: {args.workload} needs {chips} chips, found {len(devs)}",
                  file=sys.stderr)
            return 3
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from repro.launch import compile_cache

        compile_cache.enable()
    seconds = float(man["run_seconds"] if args.seconds is None else args.seconds)
    res = run_cell(H, api, jax, wl, args.seed, seconds, trace=bool(args.trace),
                   rehearse=args.rehearse, keep_trace=args.keep_trace)
    return emit(res, args.rehearse)


def run_cell(H, api, jax, wl: dict, seed: int, seconds: float, *, trace: bool = False,
             rehearse: bool = False, keep_trace: str | None = None,
             control: bool = False, t_start: float = T_START) -> dict:
    """Set up, run the window, compare, and assemble the result (a dict)."""
    from bench import compare as C

    man = H.manifest()
    cfg = H.merged(H.config(wl["config"], man), rehearse)
    mx = H.merged(H.mix(wl["traffic"]), rehearse)
    chips = int(wl["chips"])
    impl = "interpret" if rehearse else "auto"
    expect = "interpret" if rehearse else "kernel"
    counter = H.CompileCounter()
    before = H.dispatch_counts()

    job = H.make_job(cfg, mx, seed)
    blocks = job.feed.warm_up(api, job, impl)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    win = job.feed.run_window(api, job, impl, 0.0 if rehearse else seconds, blocks,
                              trace_dir=tdir, counter=counter)
    del blocks               # a pool placed on the device goes before the reference
    peak = H.memory_peak(chips)
    device = device_info(jax, chips)
    device["memory_peak_bytes"] = peak
    metrics, breakdown, notes = {}, None, []
    if trace:
        metrics, breakdown = traced_metrics(H, man, wl, job, win, tdir, device)
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        metrics = {"rows_per_s": {"value": win.rows / win.seconds, "unit": "rows/s"},
                   "setup_s": {"value": win.t0 - t_start, "unit": "s"}}
    # the comparison, once the peak is read: the program's outputs are on the
    # host, and its device arrays went with the run
    got = {"sketches": win.sketches, "rows": win.count, "outputs": win.outputs}
    sample = sorted(win.sketches)
    stated = {k: cfg["precision"][k] for k in ("sketch", "fold")}
    t_ref = time.perf_counter()
    ref = C.replay(job, win.starts, sample, stated)
    reads = C.readings(got, ref, cfg["consumers"], win.fed_rows)
    t_ref = time.perf_counter() - t_ref
    ok, checks = C.judge(reads, cfg["limits"], win.unobserved)
    # a kernel choice is counted when its program is traced: what this run
    # dispatched off the path, and whether each named kernel ever ran on it
    disp = H.dispatch_counts()
    stray = sorted(k for k, n in disp.items()
                   if n > before.get(k, 0) and not k.split("/")[1].startswith(expect))
    missing = sorted(op for op in cfg["kernels"]
                     if not any(k.startswith(op + "/" + expect) for k in disp))
    if stray or missing:
        ok = False
        metrics = {}
        notes.append(f"dispatch off the {expect} path: {stray}; kernels not seen: {missing}")
    out = {"correct": ok, "attempted": win.chunks, "failed": 0, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    diag = {"rows": win.rows, "chunks": win.chunks, "calls": win.fed_rows // job.call_rows,
            "window_s": win.seconds, "setup_s": win.t0 - t_start,
            "compiles_in_window": counter.n, "reference_s": t_ref, "dispatch": disp,
            "notes": notes,
            "call_rows": job.call_rows, "pool_calls": job.n_calls,
            "uncompared": {k: v for k, v in reads.items() if k not in checks}}
    if control:
        below = {k: C.R.BELOW[v] for k, v in stated.items()}
        ctl = C.replay(job, win.starts, sample, below, control=True)
        diag["control"] = C.readings(ctl, ref, cfg["consumers"], win.fed_rows)
        diag["control_precision"] = below
    return {"result": out, "diag": diag}


def traced_metrics(H, man, wl, job, win, tdir, device):
    from bench import trace as T

    tr = T.load(tdir)
    used = sorted(tr.devices)[: int(wl["chips"])]
    busy = T.busy_by_device(tr)
    device["busy_s"] = sum(busy.get(d, 0) for d in used) / max(1, len(used)) / 1e9
    device["window_s"] = tr.window_ns / 1e9
    ctx = MetricContext(trace=tr, job=job, window=win, devices=used,
                        peaks=H.peaks(device["kind"]) if device["platform"] == "tpu" else None)
    metrics = {}
    for pm in man["per_layer"]:
        if wl["name"] not in pm.get("workloads", [wl["name"]]):
            continue
        got = H.metric_reader(pm["name"]).read(ctx)
        if got is not None:
            metrics[pm["name"]] = {**got, "unit": pm["unit"]}
    breakdown = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
    return metrics, breakdown


class MetricContext:
    """What a per-layer metric's reader is handed."""

    def __init__(self, trace, job, window, devices, peaks):
        self.trace, self.job, self.window = trace, job, window
        self.devices, self.peaks = devices, peaks

    @property
    def chunks(self) -> int:
        return self.window.traced["chunks"]

    @property
    def steps(self) -> int:
        return self.window.traced["steps"]


def emit(res: dict, rehearse: bool) -> int:
    out, diag = res["result"], res["diag"]
    print("diag " + json.dumps(diag, default=str), file=sys.stderr)
    for name, (v, lim) in out["checks"].items():
        state = "ok" if lim is not None and v == v and v <= lim else "MISS"
        print(f"check {name} {v!r} limit {lim!r} {state}", file=sys.stderr)
    sys.stderr.flush()
    if rehearse:
        print(json.dumps({"rehearsal": "ran", "correct": out["correct"],
                          "attempted": out["attempted"], "device": out["device"],
                          "checks": out["checks"]}))
        return 0 if out["correct"] else 1
    print(json.dumps(out))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
