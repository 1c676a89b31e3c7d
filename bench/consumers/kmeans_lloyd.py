"""Consumer kind ``kmeans_lloyd``: ``SparsifiedKMeans(k, algorithm="lloyd")``, paper Alg. 1.

The program keeps every row's sketch and, at finalize, seeds K centers by
K-means++ and runs Lloyd over the sketch, best of ``n_init``. The reference
keeps its own sketch of the same chunks and checks the run by the
guarantee each step of the algorithm gives, given what the program held
before it:

- ``lloyd.seeds``: every seed the program chose is the row K-means++ picks
  with the same key, given the seeds the program chose before it. The
  reference replays each step of each hypothesis on its own sketch (the
  same D² draw over every row, 2 + ⌈ln K⌉ candidates, the least
  potential). A step whose choice differs counts, unless both are near
  ties: the program's row's potential within ``POT_TIE`` of the best, or
  a draw whose two best scores lie within ``DRAW_TIE`` with the program's
  row among the candidates or those runners-up. The first seed is
  compared exactly.
- ``lloyd.centers``: from the centers that entered the best hypothesis's
  last iteration, the reference assigns every row and applies Eq. 39 in
  float64; the relative gap of the program's final centers to that, over
  the clusters no near-tie row touches (a row whose two nearest centers
  lie within ``LABEL_TIE`` may go either way);
- ``lloyd.labels``: rows whose label is not their nearest final center,
  outside ``LABEL_TIE``;
- ``lloyd.obj``: the relative gap of ``objective_`` to the float64 sum of
  each row's distance to its labelled center;
- ``lloyd.n_iter``: the program's iterations against ``max_iter``, or
  against its own count where its last step is a fixed point;
- ``lloyd.count``: rows folded.

The reference's distances run on the device in blocks of rows against its
densified sketch, at the precision given; each row's nearest centers are
then read again in float64 on the host. The control runs the whole
algorithm with that algebra one precision below the stated one.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare as C
from bench import reference as R

PREFIX = "lloyd"
POT_TIE = 1e-5        # relative gap of two potentials read as a tie
DRAW_TIE = 1e-4       # gap of two D²-draw scores (log units) read as a tie
LABEL_TIE = 1e-5      # gap of two distances, over the row's scale, read as a tie
FIXED = 1e-5          # relative move of a last step read as a fixed point
BLOCK = 32768         # rows a block of the reference's device passes
F32 = jnp.float32


def build(api, plan, c: dict, key):
    return api.SparsifiedKMeans(int(c["k"]), plan, key=key, algorithm="lloyd",
                                n_init=int(c.get("n_init", 3)), max_iter=int(c["max_iter"]),
                                tol=float(c["tol"]))


def state(est):
    return est._reducer.retained


def extract(est) -> dict:
    return dict(seed_rows=np.asarray(est.seed_rows_), prev=np.asarray(est.prev_centers_pre_),
                centers=np.asarray(est.centers_pre_), labels=np.asarray(est.labels_),
                obj=float(est.objective_), n_iter=int(est.n_iter_), count=int(est.count_))


# ------------------------------------------------ the reference's algebra --


class Sketch:
    """The reference's retained sketch: compact on the host (float64 values)
    and densified on the device, in blocks of rows."""

    def __init__(self, vals, idx, p: int):
        self.n, self.p = vals.shape[0], p
        self.v64, self.idx = np.asarray(vals, np.float64), np.asarray(idx)
        self.z2 = np.sum(self.v64 ** 2, axis=1)
        self.block = min(BLOCK, -(-self.n // 8) * 8)
        pad = -self.n % self.block
        self.w, self.s = _dense(jnp.pad(jnp.asarray(vals, F32), ((0, pad), (0, 0))),
                                jnp.pad(jnp.asarray(idx), ((0, pad), (0, 0))), p,
                                self.block, self.n)

    def rows(self, r):
        return self.w.reshape(-1, self.p)[r]


@functools.partial(jax.jit, static_argnames=("p", "block", "n"))
def _dense(vals, idx, p: int, block: int, n: int):
    """(values, 0/1 support) densified block by block, (blocks, block, p);
    a padding row is all zeros in both."""
    rows = jnp.arange(block)[:, None]

    def one(b):
        v, i, first = b
        w = jnp.zeros((block, p), F32).at[rows, i].set(v)
        s = jnp.zeros((block, p), jnp.int8).at[rows, i].set(1)
        return w, jnp.where(first + rows < n, s, 0).astype(jnp.int8)

    nb = vals.shape[0] // block
    return jax.lax.map(one, (vals.reshape(nb, block, -1), idx.reshape(nb, block, -1),
                             jnp.arange(nb) * block))


def _block_dists(wb, sb, ctr, prec):
    """(rows, C) distances of one block's rows to ``ctr`` (C, p)."""
    z2 = jnp.sum(wb * wb, axis=1, keepdims=True)
    return (z2 - 2.0 * R.dot(wb, ctr.T, prec)
            + R.dot(sb.astype(F32), (ctr * ctr).T, prec))


@functools.partial(jax.jit, static_argnames=("n", "prec"))
def _dists(w, s, ctr, n: int, prec: str):
    """(C, n) distances of every row to ``ctr`` (C, p)."""
    d = jax.lax.map(lambda b: _block_dists(b[0], b[1], ctr, prec).T, (w, s))
    return jnp.moveaxis(d, 0, 1).reshape(ctr.shape[0], -1)[:, :n]


@functools.partial(jax.jit, static_argnames=("n", "prec", "top"))
def _nearest(w, s, ctr, n: int, prec: str, top: int):
    """(n, top) nearest centers of ``ctr`` (K, p), nearest first."""
    d = jax.lax.map(lambda b: jax.lax.top_k(-_block_dists(b[0], b[1], ctr, prec), top)[1],
                    (w, s))
    return d.reshape(-1, top)[:n]


def _dist64(sk: Sketch, centers, labels) -> np.ndarray:
    """(n,) float64 distance of every row to ``centers[labels]`` (direct form)."""
    c = np.asarray(centers, np.float64)
    return np.sum((sk.v64 - c[np.asarray(labels)[:, None], sk.idx]) ** 2, axis=1)


def _assign64(sk: Sketch, centers, prec: str):
    """(labels, ambiguous rows, their two centers) of the float64 nearest
    center among the device's two nearest."""
    top = np.asarray(_nearest(sk.w, sk.s, jnp.asarray(centers, F32), sk.n, prec, 2))
    d = np.stack([_dist64(sk, centers, top[:, j]) for j in range(2)], axis=1)
    first = np.argmin(d, axis=1)
    lab = top[np.arange(sk.n), first]
    tie = np.abs(d[:, 0] - d[:, 1]) <= LABEL_TIE * (sk.z2 + d.min(axis=1))
    return lab, tie, top[tie]


def _eq39(sk: Sketch, labels, prev) -> np.ndarray:
    """Eq. 39 in float64: each center's coordinates the mean of its rows'
    kept values, a coordinate none kept unchanged."""
    k, p = prev.shape
    key = (np.asarray(labels)[:, None] * p + sk.idx).ravel()
    sums = np.bincount(key, weights=sk.v64.ravel(), minlength=k * p).reshape(k, p)
    cnt = np.bincount(key, minlength=k * p).reshape(k, p)
    return np.where(cnt > 0, sums / np.maximum(cnt, 1), np.asarray(prev, np.float64))


@functools.partial(jax.jit, static_argnames=("n", "k", "r", "prec", "forced"))
def _kpp(key, w, s, rows, n: int, k: int, r: int, prec: str, forced: bool):
    """K-means++ of ``r`` hypotheses on the densified sketch. Free
    (``forced`` False): the seeds it picks. Forced: each step from the
    seeds ``rows`` (r, K) gives before it, with a verdict on ``rows``'s
    own choice: 0 the same, 1 a near tie, 2 another row."""
    n_cand = 2 + int(np.ceil(np.log(max(k, 2))))
    hyp = jnp.arange(r)
    split = jax.vmap(jax.random.split)
    ks = split(jax.random.split(key, r))
    first = jax.vmap(lambda kk: jax.random.randint(kk, (), 0, n))(ks[:, 0])
    flat = w.reshape(-1, w.shape[-1])
    seed0 = rows[:, 0] if forced else first
    seeds = jnp.zeros((r, k), jnp.int32).at[:, 0].set(seed0)
    verdict = jnp.zeros((r, k), jnp.int32).at[:, 0].set(jnp.where(first == seed0, 0, 2))
    min_d = _dists(w, s, flat[seed0], n, prec)

    def step(j, carry):
        seeds, verdict, min_d, key = carry
        ks = split(key)
        logits = jnp.log(jnp.maximum(min_d, 1e-30))
        scores = jax.vmap(lambda kk, lg: jax.random.gumbel(kk, (n_cand, n), F32) + lg)(
            ks[:, 1], logits)                               # (r, n_cand, n)
        cand = jnp.argmax(scores, axis=2)                   # jax.random.categorical's draw
        look = jnp.concatenate([cand, rows[:, j][:, None]], axis=1) if forced else cand
        d = _dists(w, s, flat[look.reshape(-1)], n, prec).reshape(r, look.shape[1], n)
        pots = jnp.sum(jnp.minimum(min_d[:, None], d), axis=2)
        best = jnp.argmin(pots[:, :n_cand], axis=1)
        mine = cand[hyp, best]
        if forced:
            theirs = rows[:, j]
            gap = (pots[:, n_cand] - pots[hyp, best]) / pots[hyp, best]
            top2, who = jax.lax.top_k(scores, 2)
            near = (top2[..., 0] - top2[..., 1]) <= DRAW_TIE
            among = (jnp.any(cand == theirs[:, None], axis=1)
                     | jnp.any(near & (who[..., 1] == theirs[:, None]), axis=1))
            tie = (gap <= POT_TIE) | (jnp.any(near, axis=1) & among)
            verdict = verdict.at[:, j].set(jnp.where(theirs == mine, 0, jnp.where(tie, 1, 2)))
            chosen, d_new = theirs, d[:, n_cand]
        else:
            chosen, d_new = mine, d[hyp, best]
        return (seeds.at[:, j].set(chosen), verdict, jnp.minimum(min_d, d_new), ks[:, 0])

    seeds, verdict, _, _ = jax.lax.fori_loop(1, k, step, (seeds, verdict, min_d, ks[:, 1]))
    return seeds, verdict


@functools.partial(jax.jit, static_argnames=("n", "prec"))
def _lloyd_pass(w, s, mu, n: int, prec: str):
    """Every hypothesis's labels and minimum distances (r, n), and its
    Eq. 39 sums and counts (r, K, p), at ``prec``."""
    r, k, p = mu.shape
    flat = mu.reshape(r * k, p)

    def one(b):
        wb, sb = b
        d = _block_dists(wb, sb, flat, prec).reshape(-1, r, k)
        lab = jnp.argmin(d, axis=2)                          # (rows, r)
        hot = (lab[..., None] == jnp.arange(k)).astype(F32)  # (rows, r, k)
        sums = jnp.stack([R.dot(hot[:, h].T, wb, prec) for h in range(r)])
        cnt = jnp.stack([R.dot(hot[:, h].T, sb.astype(F32), prec) for h in range(r)])
        return lab.T, jnp.min(d, axis=2).T, sums, cnt

    lab, mins, sums, cnt = jax.lax.map(one, (w, s))
    lab = jnp.moveaxis(lab, 0, 1).reshape(r, -1)[:, :n]
    mins = jnp.moveaxis(mins, 0, 1).reshape(r, -1)[:, :n]
    return lab, mins, jnp.sum(sums, axis=0), jnp.sum(cnt, axis=0)


def control_run(sk: Sketch, key, c: dict, prec: str) -> dict:
    """The whole algorithm with the reference's algebra at ``prec``: the
    control's outputs, in the shape of ``extract``'s."""
    k, r = int(c["k"]), int(c.get("n_init", 3))
    max_iter, tol = int(c["max_iter"]), float(c["tol"])
    seeds, _ = _kpp(key, sk.w, sk.s, jnp.zeros((r, k), jnp.int32), sk.n, k, r, prec, False)
    mu = sk.rows(seeds)
    prev, shift, it = mu, np.full(r, np.inf), np.zeros(r, np.int64)
    for _ in range(max_iter):
        active = (it < max_iter) & (shift > tol)
        if not active.any():
            break
        _, _, sums, cnt = _lloyd_pass(sk.w, sk.s, mu, sk.n, prec)
        new = jnp.where(cnt > 0, sums / jnp.maximum(cnt, 1.0), mu)
        moved = np.asarray(jnp.max(jnp.abs(new - mu), axis=(1, 2)))
        a = jnp.asarray(active)[:, None, None]
        prev, mu = jnp.where(a, mu, prev), jnp.where(a, new, mu)
        shift, it = np.where(active, moved, shift), it + active
    lab, mins, _, _ = _lloyd_pass(sk.w, sk.s, mu, sk.n, prec)
    obj = np.asarray(jnp.sum(mins, axis=1))
    b = int(np.argmin(obj))
    return dict(seed_rows=np.asarray(seeds), prev=np.asarray(prev[b]),
                centers=np.asarray(mu[b]), labels=np.asarray(lab[b]), obj=float(obj[b]),
                n_iter=int(it[b]), count=sk.n)


# ------------------------------------------------- the harness's hooks ---


def ref_init(ref, c: dict):
    return []


def ref_fold(ref, c: dict, st, vals, idx):
    return st + [(vals, idx)]


def ref_finalize(ref, c: dict, st, rows: int) -> dict:
    vals = jnp.concatenate([v for v, _ in st])
    idx = jnp.concatenate([i for _, i in st])
    want = dict(sketch=(np.asarray(vals), np.asarray(idx)), p_pad=ref.p_pad,
                key=ref.keys.kmeans(), prec=ref.fold, count=rows,
                max_iter=int(c["max_iter"]))
    if not ref.control:
        return want
    sk = Sketch(*want["sketch"], ref.p_pad)
    return control_run(sk, want["key"], c, ref.fold)


def readings(got: dict, want: dict) -> dict:
    sk = Sketch(*want["sketch"], want["p_pad"])
    k, r = got["centers"].shape[0], got["seed_rows"].shape[0]
    prec, max_iter = want["prec"], want["max_iter"]
    out = {"count": float(abs(got["count"] - want["count"]))}
    rows = jnp.asarray(np.clip(got["seed_rows"], 0, sk.n - 1), jnp.int32)
    _, verdict = _kpp(want["key"], sk.w, sk.s, rows, sk.n, k, r, prec, True)
    out["seeds"] = float(np.sum(np.asarray(verdict) == 2)
                         + np.sum(got["seed_rows"] >= sk.n))
    # the last iteration, from the centers that entered it
    lab, _, pairs = _assign64(sk, got["prev"], prec)
    new = _eq39(sk, lab, got["prev"])
    keep = np.ones(k, bool)
    keep[np.unique(pairs)] = False
    out["centers"] = C.rel(got["centers"][keep], new[keep])
    fixed = C.rel(new, got["prev"]) <= FIXED
    want_iter = got["n_iter"] if fixed and got["n_iter"] <= max_iter else max_iter
    out["n_iter"] = float(abs(got["n_iter"] - want_iter))
    # the labels and objective under the final centers
    labels = np.asarray(got["labels"])
    if labels.shape != (sk.n,):
        out["labels"], out["obj"] = float(sk.n + abs(labels.size - sk.n)), float("inf")
        return out
    near, _, _ = _assign64(sk, got["centers"], prec)
    d_lab = _dist64(sk, got["centers"], np.clip(labels, 0, k - 1))
    d_best = _dist64(sk, got["centers"], near)
    out["labels"] = float(np.sum((d_lab - d_best) > LABEL_TIE * (sk.z2 + d_best)))
    obj = float(np.sum(d_lab))
    out["obj"] = abs(got["obj"] - obj) / max(obj, 1e-300)
    return out


def fold_work(c: dict, shape: dict) -> tuple:
    """(operations, bytes) of one Lloyd iteration of every hypothesis over n
    retained rows, from shapes (the sparse form's essential work, not the
    densified tile the kernels contract): 3·n·m·K operations a hypothesis
    (a subtract, a square and an add per kept coordinate and center) and
    the sketch read once, n·m·8 bytes."""
    n, m = shape["n"], shape["m"]
    r, k = int(c.get("n_init", 3)), int(c["k"])
    return 3.0 * r * n * m * k, 8.0 * n * m
