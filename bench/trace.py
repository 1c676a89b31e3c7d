"""Reduce a profiler trace (``*.xplane.pb``) to what the metrics read.

    python3 bench/trace.py <trace dir or .xplane.pb>   # print a summary by hand

A device plane is ``/device:<KIND>:<n>``. Its ``XLA Ops`` line holds every
operation the device ran, and its ``XLA Modules`` line the compiled
programs (jitted functions) those operations belong to. An operation is
attributed to the module whose interval holds its start. Host spans are
the events of the host plane (``/host:CPU``), where the benchmark's own
``TraceAnnotation`` spans land on the same clock as the device.

Every reduction here is over one traced window ``[t0, t1)`` (nanoseconds),
read from the benchmark's window span.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
_COLL = r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(-start|-done)?"
COLLECTIVE_NAME = re.compile(_COLL + r"\b")
# the opcode ("all-reduce(") and not an operand that names one ("%all-reduce.3")
COLLECTIVE_OPCODE = re.compile(r"(?<![%\w.-])" + _COLL + r"\(")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: int      # ns
    end: int        # ns

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: dict        # device id -> [Op] sorted by start
    host: list           # [(name, start, end)] of host spans
    t0: int
    t1: int

    @property
    def window_ns(self) -> int:
        return self.t1 - self.t0


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return hits[-1]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]


def load(path: str) -> Trace:
    """Read the trace and cut it to the benchmark's window span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            mods = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
            starts = [s for _, s, _ in mods]
            ops = []
            for name, s, e in sorted(lines.get(OPS_LINE, []), key=lambda e: e[1]):
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][0] if i >= 0 and mods[i][2] >= s else ""
                ops.append(Op(name, mod, s, e))
            devices[int(m.group(2))] = ops
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(_events(ln))
    win = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if win:
        t0, t1 = win[0]
    else:  # no window span: the whole extent of the device operations
        allops = [o for ops in devices.values() for o in ops]
        t0 = min((o.start for o in allops), default=0)
        t1 = max((o.end for o in allops), default=0)
    return Trace(devices, host, t0, t1)


def clip(ops, t0: int, t1: int):
    """The parts of ``ops`` that lie inside [t0, t1)."""
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(Op(o.name, o.module, s, e))
    return out


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops) -> int:
    return sum(e - s for s, e in union((o.start, o.end) for o in ops))


def window_ops(tr: Trace, dev: int):
    return clip(tr.devices.get(dev, []), tr.t0, tr.t1)


def busy_by_device(tr: Trace) -> dict:
    return {d: busy_ns(window_ops(tr, d)) for d in sorted(tr.devices)}


def time_matching(tr: Trace, dev: int, pattern: re.Pattern, *, exclude=None) -> int:
    """Busy ns of the window's ops whose module or op name matches."""
    ops = [o for o in window_ops(tr, dev)
           if pattern.search(o.module) or pattern.search(o.name)]
    if exclude is not None:
        ops = [o for o in ops if not (exclude.search(o.module) or exclude.search(o.name))]
    return busy_ns(ops)


def is_collective(name: str) -> bool:
    """Whether an op event is a collective. Its name is the instruction's HLO
    text ('%all-reduce.3 = f32[...] all-reduce(...), ...') or its bare name."""
    head, eq, rhs = name.partition(" = ")
    return bool(COLLECTIVE_NAME.match(head.lstrip("%"))
                or (eq and COLLECTIVE_OPCODE.search(rhs)))


def exposed_collective_ns(tr: Trace, dev: int) -> int | None:
    """Collective time on ``dev`` during which no other operation runs there;
    None where the window holds no collective."""
    ops = window_ops(tr, dev)
    coll = [o for o in ops if is_collective(o.name)]
    if not coll:
        return None
    other = union((o.start, o.end) for o in ops if not is_collective(o.name))
    exposed = 0
    for s, e in union((o.start, o.end) for o in coll):
        covered = sum(max(0, min(e, oe) - max(s, os_)) for os_, oe in other)
        exposed += (e - s) - covered
    return exposed


def _base(name: str) -> str:
    """A module's or an op's name without its HLO text, id or fingerprint:
    'jit_spmm(4651680184421729991)' -> 'jit_spmm', '%sort.6 = (...) sort(...)'
    -> 'sort'."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\(\d+\)|[.:]\d+)$", "", name)


def top_ops(tr: Trace, n: int = 10) -> list:
    """[(module/op, seconds)] of the most time, mean over devices."""
    tot: dict = {}
    for d in tr.devices:
        for o in window_ops(tr, d):
            key = f"{_base(o.module) or '?'}/{_base(o.name)}"
            tot[key] = tot.get(key, 0) + o.dur
    nd = max(1, len(tr.devices))
    return [[k, v / nd / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10, dev: int | None = None) -> list:
    """[(what the host was doing, seconds)] over the device's idle gaps.

    Each gap is named by the shortest host span that covers at least half
    of it (else by the span that overlaps it most), and gaps are summed by
    name."""
    if not tr.devices:
        return []
    dev = min(tr.devices) if dev is None else dev
    busy = union((o.start, o.end) for o in window_ops(tr, dev))
    gaps, cur = [], tr.t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if tr.t1 > cur:
        gaps.append((cur, tr.t1))
    host = sorted((h for h in tr.host if h[0] != WINDOW_SPAN), key=lambda h: h[1])
    tot: dict = {}
    active, nxt = [], 0   # sweep: spans that started before the gap's end
    for gs, ge in gaps:
        while nxt < len(host) and host[nxt][1] < ge:
            active.append(host[nxt])
            nxt += 1
        active = [h for h in active if h[2] > gs]
        best, best_ov, best_cover = "host: no span", 0, None
        for name, s, e in active:
            ov = min(e, ge) - max(s, gs)
            if ov <= 0:
                continue
            if ov * 2 >= ge - gs and (best_cover is None or e - s < best_cover):
                best, best_cover = name, e - s
            elif best_cover is None and ov > best_ov:
                best, best_ov = name, ov
        tot[best] = tot.get(best, 0) + (ge - gs)
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def summary(tr: Trace) -> str:
    out = [f"window {tr.window_ns / 1e9:.6f} s, devices {sorted(tr.devices)}, "
           f"host spans {len(tr.host)}"]
    for d, b in busy_by_device(tr).items():
        out.append(f"  device {d}: busy {b / 1e9:.6f} s "
                   f"({100 * b / max(1, tr.window_ns):.2f} %), ops {len(window_ops(tr, d))}")
    mods: dict = {}
    for d in tr.devices:
        for o in window_ops(tr, d):
            mods[o.module] = mods.get(o.module, 0) + o.dur
    out.append("  modules by device time:")
    for k, v in sorted(mods.items(), key=lambda kv: -kv[1])[:25]:
        out.append(f"    {v / 1e9:12.6f} s  {k}")
    out.append("  ops by device time:")
    for k, v in top_ops(tr, 25):
        out.append(f"    {v:12.6f} s  {k}")
    out.append("  idle gaps by host span:")
    for k, v in idle_gaps(tr, 15):
        out.append(f"    {v:12.6f} s  {k}")
    return "\n".join(out)


if __name__ == "__main__":
    print(summary(load(sys.argv[1])))
