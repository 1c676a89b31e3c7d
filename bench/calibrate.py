#!/usr/bin/env python3
"""Read the comparison's two readings for a cell, over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 [--seconds 30]

For every seed it makes one whole run (set-up, window, comparison), as
``bench/run.py`` does, and replays the window's chunks twice: with the
reference at the precision the configuration states (the run's own
readings, the lower ones) and with the control one precision below it
(the upper ones). One JSON line per seed, then a summary line: for every
number the largest program reading and the smallest control reading. The
limits in ``bench/configs/*.json`` are set from these. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as RUN  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None, help="also append the lines to this file")
    args = ap.parse_args(argv)
    from bench import harness as H

    man = H.manifest()
    wl = H.workload(args.workload, man)
    RUN.prepare_env(args.rehearse, int(wl["chips"]))
    import jax

    api = H.import_program()
    if not args.rehearse:
        if jax.devices()[0].platform != "tpu":
            print("calibrate: no TPU found", file=sys.stderr)
            return 3
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from repro.launch import compile_cache

        compile_cache.enable()
    seconds = float(man["run_seconds"] if args.seconds is None else args.seconds)
    lines, low, up = [], {}, {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.perf_counter()
        res = RUN.run_cell(H, api, jax, wl, seed, seconds, rehearse=args.rehearse,
                           control=True, t_start=t0)
        out, diag = res["result"], res["diag"]
        line = {"seed": seed, "correct": out["correct"], "metrics": out["metrics"],
                "program": {k: v for k, (v, _) in out["checks"].items()},
                "control": diag["control"], "control_precision": diag["control_precision"],
                "chunks": diag["chunks"], "compiles_in_window": diag["compiles_in_window"],
                "setup_s": diag["setup_s"], "window_s": diag["window_s"],
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "notes": diag["notes"], "run_s": time.perf_counter() - t0}
        for k, v in line["program"].items():
            low[k] = max(low.get(k, 0.0), v)
        for k, v in line["control"].items():
            up[k] = min(up.get(k, float("inf")), v)
        lines.append(line)
        emit(line, args.out)
    emit({"summary": args.workload, "seeds": len(lines), "lower": low, "upper": up,
          "ratio": {k: (up[k] / low[k] if low.get(k) else None) for k in up}}, args.out)
    return 0


def emit(obj, path):
    s = json.dumps(obj)
    print(s, flush=True)
    if path:
        with open(path, "a") as f:
            f.write(s + "\n")


if __name__ == "__main__":
    sys.exit(main())
