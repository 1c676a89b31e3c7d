"""The comparison that decides ``correct``.

The reference (``bench/reference.py``) replays every chunk the window
folded, from the same host pool and the same key, at the precision the
configuration states; the control replays them one step below it. Both are
read against the reference by the same numbers, named here:

- ``sketch.indices``: kept coordinates that differ, over the sampled
  chunks (exact, limit 0);
- ``sketch.values``: max |a − b| / max |b| of the kept values there;
- ``rows``: rows the fold counted against rows fed (exact, limit 0);
- per consumer, the folded state and the finalized outputs, under the
  prefix and by the numbers its kind's file names
  (``bench/consumers/<kind>.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R



def exact(name: str) -> bool:
    """Numbers compared exactly (limit 0): kept indices and row counts."""
    return name in ("sketch.indices", "rows") or name.endswith(".count")


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def max_rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-300))


def subspace(a, b) -> float:
    """Sine of the largest principal angle between the row spaces of a, b."""
    qa, _ = np.linalg.qr(np.asarray(a, np.float64).T)
    qb, _ = np.linalg.qr(np.asarray(b, np.float64).T)
    s = np.linalg.svd(qa.T @ qb, compute_uv=False)
    return float(np.sqrt(max(0.0, 1.0 - float(s.min()) ** 2)))


class RefRun:
    """The reference's view of one job: its sizes, its randomness and the
    fold precision it replays at; what a consumer kind's ``ref_*`` functions
    are handed."""

    def __init__(self, job, fold: str, control: bool):
        self.job, self.fold, self.control = job, fold, control
        self.plan = job.cfg["plan"]
        self.p, self.p_pad, self.m = job.p, job.p_pad, job.m
        self.keys = R.Keys(job.key)
        self.signs = self.keys.signs(self.p_pad)
        self.signs_np = np.asarray(self.signs)
        rank = self.plan.get("rank")
        self.omega = self.keys.omega(self.p_pad, int(rank)) if rank else None


def replay(job, starts: list, sample: list, precision: dict, control: bool = False) -> dict:
    """The reference's run over the chunks the window fed, chunk j being the
    ``job.batch`` pool rows from ``starts[j]``.

    Returns ``{"sketches": {chunk: (values, indices)}, "rows": int,
    "outputs": [per consumer dict]}`` with the keys of each consumer kind's
    ``extract``. ``precision`` gives the sketch's and the folds' (see
    ``bench/reference.py``). The reference finalizes in float64; the
    ``control`` finalizes in float32 at its folds' precision where its
    kind has such a finalize."""
    from bench import harness as H

    ref = RefRun(job, precision["fold"], control)
    cons = job.cfg["consumers"]
    kinds = [H.consumer(c["kind"]) for c in cons]
    state = [k.ref_init(ref, c) for k, c in zip(kinds, cons)]
    pool = jnp.asarray(job.pool)
    slicer = jax.jit(lambda x, s: jax.lax.dynamic_slice_in_dim(x, s, job.batch))
    sketches = {}
    for j, start in enumerate(starts):
        step, shard = divmod(j, job.n_shards)
        x = slicer(pool, jnp.int32(start))
        vals, idx = R.sketch(x, ref.signs, ref.keys.mask(step, shard), ref.m, precision["sketch"])
        if j in sample:
            sketches[j] = (np.asarray(vals), np.asarray(idx))
        state = [k.ref_fold(ref, c, st, vals, idx) for k, c, st in zip(kinds, cons, state)]
    rows = len(starts) * job.batch
    outputs = [k.ref_finalize(ref, c, st, rows) for k, c, st in zip(kinds, cons, state)]
    return {"sketches": sketches, "rows": rows, "outputs": outputs}


def readings(got: dict, ref: dict, cons: list, fed_rows: int) -> dict:
    """{number: reading} of ``got`` (the program's run, or the control's)
    against ``ref``. ``got`` has the shape ``replay`` returns."""
    from bench import harness as H

    out = {}
    common = sorted(set(got["sketches"]) & set(ref["sketches"]))
    if common:
        out["sketch.indices"] = float(sum(
            int(np.sum(got["sketches"][j][1] != ref["sketches"][j][1])) for j in common))
        a = np.concatenate([got["sketches"][j][0].ravel() for j in common])
        b = np.concatenate([ref["sketches"][j][0].ravel() for j in common])
        out["sketch.values"] = max_rel(a, b)
    out["rows"] = float(abs(got["rows"] - fed_rows))
    for c, g, r in zip(cons, got["outputs"], ref["outputs"]):
        kind = H.consumer(c["kind"])
        out.update({f"{kind.PREFIX}.{k}": v for k, v in kind.readings(g, r).items()})
    return out


def judge(reads: dict, limits: dict, unobserved=()) -> tuple[bool, dict]:
    """(correct, {number: [reading, limit]}) over the numbers compared: the
    exact ones and those the configuration gives a limit, less those the
    feed says it cannot read. Every one has to lie within its limit; a NaN,
    or a limited number with no reading, fails."""
    checks, ok = {}, True
    for name in [n for n in reads if exact(n)] + [n for n in limits if n not in unobserved]:
        v = reads.get(name, float("nan"))
        lim = 0.0 if exact(name) else limits[name]
        checks[name] = [v, lim]
        if not v <= lim:
            ok = False
    return ok, checks
