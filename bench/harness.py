"""The benchmark harness: one cell, one run, driven by data.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. Everything that belongs to one of them sits in its own file,
found by name:

- ``bench/configs/<config>.json``: shapes, the ``Plan``, the consumers, the
  rows, the precision it states and the limits of the comparison;
- ``bench/mixes/<traffic>.json``: the parameters of how rows reach the job,
  and the ``feed`` that reads them;
- ``bench/feeds/<feed>.py``: the schedule, the warm-up and the window of a
  kind of traffic;
- ``bench/consumers/<kind>.py``: one consumer kind, built as users build it,
  its state read back, its reference fold and its compared numbers;
- ``bench/rows/<kind>.py``: one kind of rows, drawn on the device;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

A new cell, mix, consumer, kind of rows or metric is a new file and an
entry in ``BENCHMARK.json``; no file that is there changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHUNK_SAMPLE = 4          # sketches kept from the window for the comparison


# ------------------------------------------------------------ the files --


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(name: str, man: dict | None = None) -> dict:
    man = man or manifest()
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in man['workloads']]}")


def config(name: str, man: dict | None = None) -> dict:
    man = man or manifest()
    for c in man["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def mix(name: str) -> dict:
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


@functools.lru_cache(maxsize=None)
def plugin(folder: str, name: str):
    """The module of ``bench/<folder>/<name>.py``, loaded once."""
    path = BENCH / folder / f"{name}.py"
    if not path.is_file():
        known = sorted(f.stem for f in (BENCH / folder).glob("*.py"))
        raise KeyError(f"no bench/{folder}/{name}.py; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The reader of one per-layer metric, ``bench/metrics/<name>.py``."""
    return plugin("metrics", name)


def feed(name: str):
    """How a mix's rows reach the job, ``bench/feeds/<name>.py``."""
    return plugin("feeds", name)


def consumer(kind: str):
    """One consumer kind: built, read back and replayed, ``bench/consumers/<kind>.py``."""
    return plugin("consumers", kind)


def rows(kind: str):
    """One kind of rows, drawn on the device, ``bench/rows/<kind>.py``."""
    return plugin("rows", kind)


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json; "
                       "add its published peaks with their source")
    return table["devices"][device_kind]


def merged(cfg: dict, rehearse: bool) -> dict:
    """The configuration, with its ``rehearse`` overrides in a rehearsal."""
    if not rehearse:
        return cfg
    out = json.loads(json.dumps(cfg))
    for k, v in cfg.get("rehearse", {}).items():
        if isinstance(v, dict):
            out[k] = {**out.get(k, {}), **v}
        else:
            out[k] = v
    return out


# -------------------------------------------------------------- the job --


@dataclasses.dataclass
class Job:
    """One cell's job at one seed: shapes, the host pool and its schedule."""

    cfg: dict
    mix: dict
    seed: int
    pool: np.ndarray          # (pool rows, p) float32 on the host
    call_rows: int            # rows per partial_fit call
    key: object               # the estimators' shared PRNG key

    @property
    def plan_kw(self) -> dict:
        return dict(self.cfg["plan"])

    @property
    def batch(self) -> int:
        return int(self.cfg["plan"]["batch_size"])

    @property
    def n_shards(self) -> int:
        return int(self.cfg["plan"].get("n_shards", 1))

    @property
    def chunks_per_call(self) -> int:
        return self.call_rows // self.batch

    @property
    def n_calls(self) -> int:
        return self.pool.shape[0] // self.call_rows

    @property
    def p(self) -> int:
        return self.pool.shape[1]

    @property
    def p_pad(self) -> int:
        return 1 << max(0, (self.p - 1).bit_length())

    @property
    def m(self) -> int:
        pl = self.cfg["plan"]
        if pl.get("m") is not None:
            return int(pl["m"])
        return min(self.p_pad, max(1, int(round(float(pl["gamma"]) * self.p_pad))))

    def block(self, call: int) -> np.ndarray:
        b = call % self.n_calls
        return self.pool[b * self.call_rows:(b + 1) * self.call_rows]

    @property
    def feed(self):
        return feed(self.mix["feed"])


def make_job(cfg: dict, mx: dict, seed: int) -> Job:
    """The cell's job at ``seed``: its pool drawn on the device and copied to
    the host, in the sizes its mix's feed schedules."""
    import jax

    from bench import data

    p = data.width(cfg["data"])
    call_rows, n_calls = feed(mx["feed"]).schedule(cfg, mx, p)
    pool = data.pool(seed, cfg["data"], n_calls * call_rows // int(cfg["plan"]["batch_size"]),
                     int(cfg["plan"]["batch_size"]))
    key = jax.random.fold_in(data.root_key(seed), 11)
    return Job(cfg, mx, seed, pool, call_rows, key)


# ---------------------------------------------------------- the program --


def import_program():
    """The system under test, from the checkout's ``src``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.api as api          # noqa: F401  (raises where it is missing)

    return api


def build_consumers(api, job: Job, impl: str):
    """The cell's ``Plan`` and its consumers, each built by its kind's file."""
    plan = api.Plan(**{**job.plan_kw, "impl": impl})
    return plan, [consumer(c["kind"]).build(api, plan, c, job.key) for c in job.cfg["consumers"]]


def extract(consumers, job: Job) -> list:
    """What the timed path produced, on the host: each consumer's fold state
    and finalized outputs, by its kind's ``extract``."""
    return [consumer(c["kind"]).extract(est) for est, c in zip(consumers, job.cfg["consumers"])]


def quiet(consumers, job: Job) -> None:
    """Wait until every consumer's folded state is on the device."""
    import jax

    jax.block_until_ready([consumer(c["kind"]).state(est)
                           for est, c in zip(consumers, job.cfg["consumers"])])


def dispatch_counts() -> dict:
    from repro import obs

    return {f"{m.labels['op']}/{m.labels['path']}": int(m.value)
            for m in obs.default_registry().metrics() if m.name == "kernels.dispatch"}


class CompileCounter:
    """Counts the programs JAX lowers while ``on`` (each one is then compiled
    or loaded from the persistent cache)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.n += 1


# ------------------------------------------------------------ the window --


@dataclasses.dataclass
class Window:
    """What a feed's window hands back (``bench/feeds/<feed>.py``)."""

    t0: float                # perf_counter at the window's start
    rows: int                # rows the window folded (its throughput's numerator)
    count: int               # rows the run folded, its first call's with them
    seconds: float
    outputs: list            # extract() per consumer
    sketches: dict           # chunk -> (values, indices) numpy; {} where unseen
    fed_rows: int
    starts: list             # pool row of every chunk the run folded, in order
    call_seconds: list       # host seconds of every call in the window
    traced: dict | None      # chunks/steps/calls inside the traced span
    unobserved: tuple = ()   # numbers this feed cannot read (no sketches seen)

    @property
    def chunks(self) -> int:
        return len(self.starts)


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def memory_peak(n_devices: int) -> int | None:
    import jax

    peaks_ = []
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None
